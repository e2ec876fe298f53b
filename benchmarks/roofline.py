"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh).

Reads the dry-run artifacts (benchmarks/artifacts/dryrun/...) and derives

    compute term    = HLO_FLOPs_per_device / peak_FLOPs        (s)
    memory term     = HLO_bytes_per_device / HBM_bw            (s)
    collective term = collective_bytes_per_device / link_bw    (s)

The SPMD HLO module is the per-device program, so cost_analysis() numbers
are already per chip.  MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill/decode)
with N = active params for MoE; the ratio MODEL_FLOPS/HLO_FLOPs exposes
remat/redundancy waste.  Usage:

    PYTHONPATH=src python -m benchmarks.roofline [--mesh single] [--md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip (taken
# here as four links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# the dry-run artifacts are compiled for a v5e target
DRYRUN_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source") from None


ART = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")


def fused_relax_roofline(scale: int = 10, deg: int = 8,
                         fused_rounds: int = 4, block_v: int = 256,
                         tile_e: int = 256, reps: int = 3) -> dict:
    """Roofline terms for the fused relaxation megakernel (measured).

    Unlike the dry-run artifacts above, this drives the actual
    ``edge_relax_fused`` megakernel (interpret mode on CPU) through a
    whole blocked-backend solve and derives per-invocation traffic from
    the kernel's own counters — the same numbers the in-kernel metrics
    fold produces, so nothing is recomputed host-side:

      edge bytes  = n_tiles_scanned * tile_e * 16   (src/dst/w reads +
                    the dist gather, 4 B per edge slot)
      state bytes = n_rounds * n_out * 21           (dist/parent
                    read+write + frontier read+write + deg read)
      FLOPs       = 2 * nTrav + 2 * n_tiles_scanned * tile_e
                    (add + window compare on in-window edges, plus the
                    scheduled compare-plane min per edge slot)

    ``achieved_*`` divides those totals by measured wall time;
    ``peak_frac_*`` compares against the running device's entry in
    :data:`PEAKS` (a device without one, such as the CPU, raises).
    ``rounds_per_invocation`` is the fusion win itself (1.0 ≡ unfused).
    """
    import time

    import jax
    import numpy as np

    from repro.core.sssp import sssp
    from repro.data.generators import kronecker

    pk = peaks(jax.devices()[0].device_kind)
    g = kronecker(scale, deg, seed=2)
    dg = g.to_device()
    source = int(np.argmax(np.asarray(g.deg)))

    def solve():
        d, p, m = sssp(dg, source, backend="blocked_pallas",
                       fused_rounds=fused_rounds, block_v=block_v,
                       tile_e=tile_e)
        jax.block_until_ready(d)
        return m

    m = solve()                                   # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        m = solve()
    time_s = (time.perf_counter() - t0) / reps

    inv = max(float(m.n_invocations), 1.0)
    rounds = float(m.n_rounds)
    tiles = float(m.n_tiles_scanned)
    n_out = -(-g.n // block_v) * block_v
    edge_bytes = tiles * tile_e * 16.0
    state_bytes = rounds * n_out * 21.0
    byts = edge_bytes + state_bytes
    flops = 2.0 * float(m.n_trav) + 2.0 * tiles * tile_e
    return {
        "arch": "edge_relax_fused", "shape": f"kron{scale}x{deg}",
        "mesh": "single",
        "fused_rounds": fused_rounds,
        "time_s": time_s,
        "time_s_per_invocation": time_s / inv,
        "rounds_per_invocation": rounds / inv,
        "invocations_per_solve": inv,
        "bytes_per_invocation": byts / inv,
        "flops_per_invocation": flops / inv,
        "achieved_bw": byts / time_s,
        "achieved_flops": flops / time_s,
        "peak_frac_bw": byts / time_s / pk["hbm_bw"],
        "peak_frac_flops": flops / time_s / pk["flops"],
        "compute_s": flops / pk["flops"],
        "memory_s": byts / pk["hbm_bw"],
        "collective_s": 0.0,
        "useful_ratio": None,
        "roofline_fraction": ((flops / pk["flops"]) /
                              max(flops / pk["flops"],
                                  byts / pk["hbm_bw"])),
        "dominant": ("memory" if byts / pk["hbm_bw"] > flops / pk["flops"]
                     else "compute"),
    }


def model_flops_per_device(art: dict) -> float | None:
    meta = art.get("meta", {})
    n_dev = 1
    for v in art.get("mesh_shape", {}).values():
        n_dev *= v
    tokens = meta.get("tokens")
    n_active = meta.get("active_params") or meta.get("params")
    if tokens is None or n_active is None:
        return None
    shape = art.get("shape", "")
    if shape.startswith("train"):
        return 6.0 * n_active * tokens / n_dev
    # prefill/decode/serve: forward only
    return 2.0 * n_active * tokens / n_dev


def analyze(art: dict, device_kind: str = DRYRUN_KIND) -> dict:
    pk = peaks(device_kind)
    cost = art.get("cost", {})
    coll = art.get("collectives", {})
    # cost_analysis (and the HLO text) count scan/while bodies ONCE; the
    # layer stack / microbatch loop / triplet chunks are scans, so scale by
    # the static trip product recorded at cell-build time.  This slightly
    # overcounts the once-per-step tail (embedding, optimizer) — noted in
    # EXPERIMENTS.md §Methodology.
    mult = max(int(art.get("meta", {}).get("scan_mult", 1)), 1)
    flops = cost.get("flops", 0.0) * mult
    byts = cost.get("bytes accessed", 0.0) * mult
    cbytes = float(coll.get("total", 0)) * mult
    terms = {
        "compute_s": flops / pk["flops"],
        "memory_s": byts / pk["hbm_bw"],
        "collective_s": cbytes / pk["ici_bw"],
    }
    dom = max(terms, key=terms.get)
    bound_s = terms[dom]
    mf = model_flops_per_device(art)
    out = {
        "arch": art["arch"], "shape": art["shape"], "mesh": art["mesh"],
        **terms,
        "dominant": dom.replace("_s", ""),
        "model_flops_per_dev": mf,
        "hlo_flops_per_dev": flops,
        "useful_ratio": (mf / flops) if (mf and flops) else None,
        "roofline_fraction": (terms["compute_s"] / bound_s) if bound_s else None,
        "mem_temp_gb": art.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9,
        "mem_args_gb": art.get("arg_bytes_per_device", 0) / 1e9,
        "collect_ring_gb": coll.get("ring_bytes", 0) * (
            max(int(art.get("meta", {}).get("scan_mult", 1)), 1)) / 1e9,
        "n_while": art.get("n_while_loops", 0),
        "scan_mult": max(int(art.get("meta", {}).get("scan_mult", 1)), 1),
    }
    return out


def load_all(mesh: str):
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, mesh, "*.json"))):
        with open(path) as f:
            art = json.load(f)
        if art.get("ok"):
            rows.append(analyze(art))
    return rows


def to_markdown(rows):
    hdr = ("| arch | shape | compute(ms) | memory(ms) | collective(ms) | "
           "bound | MODEL/HLO | peak-frac |")
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        ur = f"{r['useful_ratio']:.2f}" if r["useful_ratio"] else "—"
        rf = f"{r['roofline_fraction']:.2f}" if r["roofline_fraction"] else "—"
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']*1e3:.2f} | "
            f"{r['memory_s']*1e3:.2f} | {r['collective_s']*1e3:.2f} | "
            f"{r['dominant']} | {ur} | {rf} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--fused", action="store_true",
                    help="also measure the fused relaxation megakernel "
                         "(runs a real solve; needs PYTHONPATH=src)")
    ap.add_argument("--fused-scale", type=int, default=10)
    args = ap.parse_args()
    rows = load_all(args.mesh)
    if args.fused:
        r = fused_relax_roofline(scale=args.fused_scale)
        rows.append(r)
        print(f"# fused_relax kron{args.fused_scale}: "
              f"{r['rounds_per_invocation']:.2f} rounds/invocation, "
              f"{r['bytes_per_invocation']:.3g} B + "
              f"{r['flops_per_invocation']:.3g} FLOP per invocation, "
              f"achieved {r['achieved_bw']:.3g} B/s "
              f"({r['peak_frac_bw']:.2e} of HBM peak) / "
              f"{r['achieved_flops']:.3g} FLOP/s "
              f"({r['peak_frac_flops']:.2e} of peak) -> {r['dominant']}")
    if args.md:
        print(to_markdown(rows))
    else:
        for r in rows:
            print(f"{r['arch']}/{r['shape']}: comp={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms -> {r['dominant']} "
                  f"(useful={r['useful_ratio'] or float('nan'):.2f})"
                  if r['useful_ratio'] else
                  f"{r['arch']}/{r['shape']}: comp={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms -> {r['dominant']}")
    out = args.json_out or os.path.join(
        os.path.dirname(__file__), "artifacts", f"roofline_{args.mesh}.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows -> {out}")


if __name__ == "__main__":
    main()
