"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --sssp --mesh both

Artifacts (memory analysis, cost analysis, collective-byte breakdown) are
written to benchmarks/artifacts/dryrun/<mesh>/<arch>__<shape>.json and
reused by benchmarks/roofline.py.  Completed cells are skipped on re-runs
unless --force.
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; this
# must run before ANY other import, since jax locks the device count on
# first init.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import numpy as np   # noqa: E402
import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                       # noqa: E402
from repro.launch import cells, hlo_stats       # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None:
        return {"available": False}
    out = {"available": True}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _arg_bytes_per_device(args, n_dev):
    """Analytic per-device argument bytes from struct shardings."""
    total = 0
    for leaf in jax.tree.leaves(args):
        size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "num_devices"):
            shard = sh.shard_shape(leaf.shape)
            size = int(np.prod(shard)) * leaf.dtype.itemsize
        total += size
    return total


def run_cell(arch: str, shape: str, mesh_kind: str, force: bool = False,
             out_dir: str = ART_DIR):
    os.makedirs(os.path.join(out_dir, mesh_kind), exist_ok=True)
    path = os.path.join(out_dir, mesh_kind, f"{arch}__{shape}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            art = json.load(f)
        if art.get("ok"):
            print(f"[skip] {mesh_kind}/{arch}/{shape} (cached)")
            return art
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    art = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "mesh_shape": dict(mesh.shape), "ok": False}
    try:
        fn, args, meta, out_sh = cells.build_cell(arch, shape, mesh)
        art["meta"] = {k: (int(v) if isinstance(v, (int, np.integer))
                           else v) for k, v in meta.items()}
        jitted = jax.jit(fn) if out_sh is None else \
            jax.jit(fn, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = compiled.cost_analysis() or {}
        art["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and not k.startswith("utilization")}
        art["memory"] = _mem_dict(compiled)
        n_dev = int(np.prod(list(mesh.shape.values())))
        art["arg_bytes_per_device"] = _arg_bytes_per_device(args, n_dev)
        hlo = compiled.as_text()
        art["collectives"] = hlo_stats.collective_bytes(hlo)
        art["n_while_loops"] = hlo_stats.while_trip_note(hlo)
        art["timing"] = {"lower_s": round(t_lower, 1),
                         "compile_s": round(t_compile, 1)}
        art["ok"] = True
        print(f"[ok] {mesh_kind}/{arch}/{shape}: "
              f"flops/dev={art['cost'].get('flops', 0):.3e} "
              f"coll={art['collectives']['total']/1e9:.3f}GB "
              f"mem(temp)={art['memory'].get('temp_size_in_bytes', -1)/1e9:.2f}GB "
              f"compile={t_compile:.0f}s")
        print(f"     memory_analysis: {art['memory']}")
        print(f"     cost_analysis: flops={art['cost'].get('flops')} "
              f"bytes={art['cost'].get('bytes accessed')}")
    except Exception as e:  # noqa: BLE001 - record failures in the artifact
        art["error"] = f"{type(e).__name__}: {e}"
        art["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {mesh_kind}/{arch}/{shape}: {art['error']}")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return art


def run_sssp(mesh_kind: str, scale: int = 26, edge_factor: int = 16,
             version: str = "v2", force: bool = False,
             out_dir: str = ART_DIR):
    """Dry-run the distributed SSSP engine on a Graph500-scale struct."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as dist
    from repro.core import stepping
    from repro.core.graph import RATIO_NUM

    os.makedirs(os.path.join(out_dir, mesh_kind), exist_ok=True)
    name = f"sssp-{version}-gr{scale}_{edge_factor}"
    path = os.path.join(out_dir, mesh_kind, f"{name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            art = json.load(f)
        if art.get("ok"):
            print(f"[skip] {mesh_kind}/{name} (cached)")
            return art
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axes = tuple(mesh.axis_names)
    p = int(np.prod(list(mesh.shape.values())))
    n = 1 << scale
    m = 2 * edge_factor * n
    block = n // p
    e_max = m // p
    art = {"arch": name, "shape": f"n=2^{scale},ef={edge_factor}",
           "mesh": mesh_kind, "ok": False}
    t0 = time.time()
    try:
        def S(shape, dt, spec):
            return jax.ShapeDtypeStruct(shape, dt,
                                        sharding=NamedSharding(mesh, spec))
        sg = dist.ShardedGraph(
            src=S((p, e_max), jnp.int32, P(axes)),
            dst=S((p, e_max), jnp.int32, P(axes)),
            w=S((p, e_max), jnp.float32, P(axes)),
            deg=S((p, block), jnp.int32, P(axes)),
            rtow=S((RATIO_NUM,), jnp.float32, P()),
            n_edges2=S((), jnp.int32, P()),
            n_true=S((), jnp.int32, P()))
        src_s = S((), jnp.int32, P())
        gp_s = S((), jnp.int32, P())        # "tree" goal parameter
        params = stepping.SteppingParams()
        if version == "v1":
            body = dist._v1_body(n, block, axes, params, 1 << 20)
            out_specs = (P(), P(), P())
        elif version == "v3":
            body = dist._v2_body(n, block, axes, params, 1 << 20, 0,
                                 tuple(mesh.shape[a] for a in axes),
                                 compact_capacity=max(block // 16, 8))
            out_specs = (P(axes), P(axes), P())
        else:
            body = dist._v2_body(n, block, axes, params, 1 << 20, 0,
                                 tuple(mesh.shape[a] for a in axes))
            out_specs = (P(axes), P(axes), P())
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(dist.graph_specs(axes), P(), P()),
                           out_specs=out_specs, check_vma=False)
        lowered = jax.jit(fn).lower(sg, src_s, gp_s)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, list):     # older jax: one dict per partition
            cost = cost[0] if cost else {}
        art["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))}
        art["memory"] = _mem_dict(compiled)
        hlo = compiled.as_text()
        art["collectives"] = hlo_stats.collective_bytes(hlo)
        art["n_while_loops"] = hlo_stats.while_trip_note(hlo)
        art["note"] = ("cost/collectives are per while-iteration x1; "
                       "multiply by measured round counts (benchmarks)")
        art["timing"] = {"total_s": round(time.time() - t0, 1)}
        art["ok"] = True
        print(f"[ok] {mesh_kind}/{name}: coll/iter="
              f"{art['collectives']['total']/1e6:.1f}MB "
              f"t={art['timing']['total_s']}s")
        print(f"     memory_analysis: {art['memory']}")
        print(f"     cost_analysis: flops={art['cost'].get('flops')}")
    except Exception as e:  # noqa: BLE001
        art["error"] = f"{type(e).__name__}: {e}"
        art["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {mesh_kind}/{name}: {art['error']}")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-bonus", action="store_true")
    ap.add_argument("--sssp", action="store_true")
    ap.add_argument("--sssp-version", default="v2")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = []
    if args.sssp:
        for mk in meshes:
            results.append(run_sssp(mk, version=args.sssp_version,
                                    force=args.force))
    elif args.all:
        for mk in meshes:
            for arch, shape in configs.all_cells(
                    include_bonus=args.include_bonus):
                results.append(run_cell(arch, shape, mk, args.force))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape required unless --all or --sssp")
        for mk in meshes:
            results.append(run_cell(args.arch, args.shape, mk, args.force))

    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n=== dry-run: {n_ok}/{len(results)} cells compiled ===")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
