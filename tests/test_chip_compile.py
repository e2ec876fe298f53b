"""TPU v5e compiles of the main path, made without a chip.

The installed TPU compiler compiles for a described (not attached) v5e
chip from shapes alone, and refuses what the chip would refuse: blocks
that do not match the XLA tiling, too much VMEM, a program that does not
fit HBM.  Nothing runs here; the interpret-mode parity tests cover the
results.  The topology is described inside a module fixture, so only the
worker given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import relax, sssp
from repro.core.config import ConfigError
from repro.core.graph import RATIO_NUM, DeviceGraph
from repro.kernels.edge_relax.edge_relax import (
    DEFAULT_BLOCK_V as BV, DEFAULT_TILE_E as TE, edge_relax,
    edge_relax_fused, edge_relax_partials)

HBM_BYTES = 16 * 10**9          # one v5e chip
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of the cache altogether."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("alt", [False, True], ids=["plain", "alt"])
def test_edge_relax_compiles_for_v5e(one_chip, no_cache, alt):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    nt, nd = 64, 8
    args = [S((BV,), F32), S((BV,), jnp.int8), S((nt * TE,), I32),
            S((nt * TE,), I32), S((nt * TE,), F32), S((nt,), I32),
            S((nt,), jnp.bool_), S((nd,), jnp.bool_), S((), F32), S((), F32)]
    if alt:
        args += [S((nd * BV,), F32), S((), F32)]
    _kernel_compiled(_compile(
        lambda *a: edge_relax(*a, block_v=BV, tile_e=TE, n_dst_blocks=nd,
                              interpret=False), *args))


@pytest.mark.parametrize("alt", [False, True], ids=["plain", "alt"])
def test_edge_relax_partials_compiles_for_v5e(one_chip, no_cache, alt):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    nt, nd, n_src_blocks = 64, 16, 4
    e = n_src_blocks * nt * TE
    args = [S((n_src_blocks * BV,), F32), S((n_src_blocks * BV,), I32),
            S((n_src_blocks * BV,), I32), S((e,), I32), S((e,), I32),
            S((e,), F32), S((n_src_blocks * nt,), I32),
            S((n_src_blocks * nt,), jnp.bool_), S((n_src_blocks * nt,), I32),
            S((), F32), S((), F32)]
    if alt:
        args += [S((nd * BV,), F32), S((), F32)]
    _kernel_compiled(_compile(
        lambda *a: edge_relax_partials(*a, block_v=BV, tile_e=TE,
                                       n_dst_blocks=nd, interpret=False),
        *args))


def test_fused_megakernel_is_refused_compiled():
    """The fused kernel keeps the whole slab in VMEM and gathers by
    vector index; compiled, it is refused with the reason, never run
    interpreted in its place."""
    n, e = 2 * BV, 4 * TE
    z = lambda shape, dt: jnp.zeros(shape, dt)
    with pytest.raises(ConfigError, match="fused"):
        edge_relax_fused(z((n,), F32), z((n,), I32), z((n,), bool),
                         z((n,), I32), z((e,), I32), z((e,), I32),
                         z((e,), F32), z((4,), I32), z((4,), bool),
                         0.5, 1.0, interpret=False)


def _kron_shapes(sharding, scale: int, edge_factor: int = 16,
                 directed: bool = False):
    """A ``DeviceGraph`` of shapes for ``kronecker(scale, edge_factor)``:
    each edge both ways, or each arc once where ``directed``."""
    n = 1 << scale
    m = (1 if directed else 2) * edge_factor * n
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return DeviceGraph(src=S((m,), I32), dst=S((m,), I32), w=S((m,), F32),
                       row_ptr=S((n + 1,), I32), deg=S((n,), I32),
                       rtow=S((RATIO_NUM,), F32), max_w=S((), F32),
                       n_edges2=S((), I32), directed=directed)


@pytest.mark.parametrize("batch", [0, 2], ids=["single", "batch2"])
def test_segment_min_solve_compiles_at_scale22(one_chip, no_cache, batch):
    """The jitted single-device solve at the real-size cell's shapes fits
    one chip's HBM (a batch of 4 does not: XLA reports 17.2 of 15.75 GB,
    so the smoke run batches 2)."""
    g = _kron_shapes(one_chip, 22)
    S = lambda shape: jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)
    be = relax.get_backend("segment_min")
    if batch:
        lowered = sssp._sssp_batch_jit.lower(
            g, g, S((batch,)), be, 1_000_000, 3.0, 0.9, "tree", S((batch,)))
    else:
        lowered = sssp._sssp_jit.lower(g, g, S(()), be, 1_000_000, 3.0, 0.9,
                                       "tree", S(()))
    mem = lowered.compile().memory_analysis()
    # the graph is passed twice (graph and its own layout), so the
    # argument bytes count it twice; the buffers are shared at run time
    assert mem.argument_size_in_bytes // 2 + mem.temp_size_in_bytes \
        < HBM_BYTES


def test_directed_solve_compiles_for_the_kron17d_cell(one_chip, no_cache):
    """The directed cell's program (the pull over arcs read forwards, the
    out-degree-0 leaf rule) at its 2,097,152 arcs fits one chip's HBM,
    and the mark alone makes it another program than the undirected
    one on the same shapes."""
    be = relax.get_backend("segment_min")
    S = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    g = _kron_shapes(one_chip, 17, directed=True)
    compiled = {d: sssp._sssp_jit.lower(
        g._replace(directed=d), g._replace(directed=d), S, be, 1_000_000,
        3.0, 0.9, "tree", S).compile() for d in (True, False)}
    mem = compiled[True].memory_analysis()
    assert mem.argument_size_in_bytes // 2 + mem.temp_size_in_bytes \
        < HBM_BYTES
    assert compiled[True].as_text() != compiled[False].as_text()
