"""Compile the training path's TPU programs for a described (not attached)
TPU v5e, at the shapes of a reddit-width measured step (batch 2000,
fan-out (10, 25), 602-wide features).

Nothing runs: the TPU compiler refuses here what it would refuse on the
chip (unaligned block shapes, too much VMEM, a kernel without a VJP), at
no chip time. The topology is described inside a fixture, never at import:
only one process may load the TPU library, and test workers import every
test file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# shapes of one reddit-width batch after pow2 bucketing (train/compute.py)
TILES_L0, SRC_ROWS_L0, DST_ROWS_L0 = 65_536, 131_072, 32_768
TILES_L1, DST_ROWS_L1 = 2_048, 2_048
N_FEAT = 602
CACHE_ROWS = 81_537   # cache_frac 0.35 of reddit's 232,965 nodes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The described chip is not the backend, so ``default_interpret``
    would pick the CPU interpreter; force the compiled kernel."""
    from repro.kernels.segment_mm import kernel

    monkeypatch.setattr(kernel, "default_interpret", lambda: False)


def _spmm_args(sds, tiles, src_rows, f):
    return (
        sds((tiles,), jnp.int32), sds((tiles,), jnp.int32),
        sds((tiles, 128, 128)), sds((src_rows, f)),
    )


@pytest.mark.parametrize("tiles,src_rows,dst_rows,f", [
    (TILES_L0, SRC_ROWS_L0, DST_ROWS_L0, 640),   # layer 0: 602 -> 640 lanes
    (TILES_L1, DST_ROWS_L0, DST_ROWS_L1, 128),   # layer 1: 16 -> 128 lanes
])
def test_spmm_forward_compiles(sds, tiles, src_rows, dst_rows, f):
    from repro.kernels.segment_mm.kernel import block_spmm_kernel

    def fwd(r, c, b, x):
        return block_spmm_kernel(r, c, b, x, dst_rows // 128,
                                 interpret=False)

    exe = jax.jit(fwd).lower(*_spmm_args(sds, tiles, src_rows, f)).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_spmm_vjp_compiles(sds):
    from repro.kernels.segment_mm.kernel import block_spmm_kernel

    def vjp(r, c, b, x, dy):
        _, pull = jax.vjp(
            lambda x: block_spmm_kernel(r, c, b, x, DST_ROWS_L1 // 128,
                                        interpret=False), x,
        )
        return pull(dy)[0]

    args = _spmm_args(sds, TILES_L1, DST_ROWS_L0, 128) + (
        sds((DST_ROWS_L1, 128)),
    )
    exe = jax.jit(vjp).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_embedding_gather_compiles(sds):
    from repro.kernels.embedding_bag.kernel import gather_rows_kernel

    table = sds((CACHE_ROWS, 1, -(-N_FEAT // 128) * 128))
    exe = gather_rows_kernel.lower(
        sds((65_536,), jnp.int32), table, interpret=False,
    ).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_sage_step_compiles(sds, compiled_kernels):
    import types

    from repro.train import gnn_trainer as gt
    from repro.train.compute import ComputeEngine

    graph = types.SimpleNamespace(
        features=np.zeros((1, N_FEAT), np.float32),
        labels=np.arange(41, dtype=np.int32),
    )
    eng = ComputeEngine(graph, gt.RunConfig(), agg_impl="pallas")

    def shapes(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    def layer(tiles, dst_rows, last=False):
        out = {
            "rows": sds((tiles,), jnp.int32),
            "cols": sds((tiles,), jnp.int32),
            "blocks": sds((tiles, 128, 128)),
            "counts": sds((dst_rows, 1)),
            "dst_pos": sds((dst_rows,), jnp.int32),
        }
        if last:
            out["labels"] = sds((dst_rows,), jnp.int32)
            out["lmask"] = sds((dst_rows,))
        return out

    layers = (layer(TILES_L0, DST_ROWS_L0),
              layer(TILES_L1, DST_ROWS_L1, last=True))
    exe = eng._jit.lower(
        shapes(eng.params), shapes(eng.opt_state), shapes(eng.error),
        sds((SRC_ROWS_L0, N_FEAT)), layers,
    ).compile()
    mem = exe.memory_analysis()
    print(mem)
    # two forward aggregations and the layer-1 backward run as kernels
    assert exe.as_text().count("tpu_custom_call") == 3
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
