"""Share of the HBM roofline that the window's logical work reaches.

The least bytes the solves' exact counters need (``bench/work.py``),
at the chip's published HBM bandwidth, over the device busy time of
the traced window."""
from bench import work


def read(rec):
    trace, peaks, trees = rec["trace"], rec["peaks"], rec["trees"]
    if trace is None or peaks is None or not trees or trace["busy_s"] <= 0:
        return None
    nbytes = work.min_bytes(sum(t["n_trav"] for t in trees),
                            sum(t["n_pull_trav"] for t in trees),
                            sum(t["n_updates"] for t in trees))
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / trace["busy_s"]
