"""Cell benchmark of the SSSP engine: one cell per ``BENCHMARK.json`` workload."""
