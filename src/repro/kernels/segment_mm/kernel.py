"""Block-sparse SpMM Pallas kernel — the TPU-native GNN aggregation.

GPU GNN systems scatter messages with atomics; TPUs have no atomics, so we
re-tile the adjacency into (TN x TM) blocks over (dst, src), sort blocks by
destination row, and let each grid step do one MXU matmul

    acc[TN, TF] += A_block[TN, TM] @ X_block[TM, TF]

into a VMEM accumulator that is flushed when the destination row-block
changes (revisit-consecutive output pattern). Scalar-prefetched block
row/col ids drive the BlockSpec index maps. This is the hardware adaptation
recorded in DESIGN.md §6: scatter-atomics -> destination-tiled block-sparse
matmul.

The backward pass dX = Aᵀ·dY runs the same kernel over the same tile
array: a source-sorted permutation of the blocks drives the index maps
(output block = the block's column, input block = its row) and the kernel
body contracts each tile over its destination axis, so no transposed copy
of the tiles is ever materialized. The tiles are treated as constants
(0/1 edge masks): they get no gradient.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def default_interpret() -> bool:
    """Interpret only when no accelerator backend is attached.

    ``interpret=None`` everywhere in this package means "ask the backend":
    on TPU/GPU the kernel compiles natively; on CPU it falls back to the
    Pallas interpreter (slow, but exact — the parity tests run there).
    """
    return jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")


def _spmm_kernel(transpose, *refs):
    # refs: out_ids, in_ids[, tile_ids] (scalar prefetch), tiles, x, o, acc
    out_ids = refs[0]
    blocks_ref, x_ref, o_ref, acc_ref = refs[-4:]
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    row = out_ids[b]
    prev = out_ids[jnp.maximum(b - 1, 0)]
    nxt = out_ids[jnp.minimum(b + 1, nb - 1)]

    @pl.when((b == 0) | (prev != row))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = blocks_ref[0]
    if transpose:
        # acc[TM, TF] += A_block[TN, TM]ᵀ @ dY_block[TN, TF]
        acc_ref[...] += jax.lax.dot_general(
            tile, x_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        acc_ref[...] += jnp.dot(
            tile, x_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when((b == nb - 1) | (nxt != row))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _spmm_call(out_ids, in_ids, tile_ids, blocks, x, n_out_blocks,
               tn, tm, tf, interpret):
    """One pass over the blocks in ``out_ids`` order.

    Forward (``tile_ids is None``): block b multiplies tile b by input
    row-block ``in_ids[b]`` into output block ``out_ids[b]``. Transposed
    (``tile_ids`` given): step b reads tile ``tile_ids[b]`` and contracts
    it over its first axis.
    """
    transpose = tile_ids is not None
    nb = blocks.shape[0]
    f = x.shape[1]
    assert f % tf == 0
    in_rows, out_rows = (tn, tm) if transpose else (tm, tn)
    assert x.shape[0] % in_rows == 0
    prefetch = (out_ids, in_ids) + ((tile_ids,) if transpose else ())

    if transpose:
        def tile_map(fi, b, out_ids, in_ids, tile_ids):
            return (tile_ids[b], 0, 0)
    else:
        def tile_map(fi, b, out_ids, in_ids):
            return (b, 0, 0)

    def in_map(fi, b, out_ids, in_ids, *_):
        return (in_ids[b], fi)

    def out_map(fi, b, out_ids, *_):
        return (out_ids[b], fi)

    return pl.pallas_call(
        partial(_spmm_kernel, transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(f // tf, nb),
            in_specs=[
                pl.BlockSpec((1, tn, tm), tile_map),
                pl.BlockSpec((in_rows, tf), in_map),
            ],
            out_specs=pl.BlockSpec((out_rows, tf), out_map),
            scratch_shapes=[pltpu.VMEM((out_rows, tf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out_blocks * out_rows, f), x.dtype),
        interpret=interpret,
    )(*prefetch, blocks, x)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _block_spmm(rows, cols, blocks, x, n_dst_blocks, tn, tm, tf, interpret):
    return _spmm_call(rows, cols, None, blocks, x, n_dst_blocks,
                      tn, tm, tf, interpret)


def _block_spmm_fwd(rows, cols, blocks, x, n_dst_blocks, tn, tm, tf,
                    interpret):
    y = _block_spmm(rows, cols, blocks, x, n_dst_blocks, tn, tm, tf,
                    interpret)
    # a zero-width stand-in carries x's row count to the backward pass
    # without keeping x itself alive
    return y, (rows, cols, blocks, jnp.zeros((x.shape[0], 0), x.dtype))


def _block_spmm_bwd(n_dst_blocks, tn, tm, tf, interpret, res, dy):
    rows, cols, blocks, x_like = res
    n_src_blocks = x_like.shape[0] // tm
    perm = jnp.argsort(cols, stable=True).astype(jnp.int32)
    dx = _spmm_call(cols[perm], rows[perm], perm, blocks, dy, n_src_blocks,
                    tn, tm, tf, interpret)
    # source blocks no tile points at receive no write: their gradient is 0
    covered = jnp.zeros(n_src_blocks, bool).at[cols].set(True)
    dx = jnp.where(jnp.repeat(covered, tm)[:, None], dx, 0)
    return None, None, None, dx


_block_spmm.defvjp(_block_spmm_fwd, _block_spmm_bwd)


@partial(
    jax.jit,
    static_argnames=("n_dst_blocks", "tn", "tm", "tf", "interpret"),
)
def block_spmm_kernel(
    rows: jax.Array,     # (nb,) int32 block-row ids, sorted ascending
    cols: jax.Array,     # (nb,) int32 block-col ids
    blocks: jax.Array,   # (nb, TN, TM) dense adjacency blocks (constants)
    x: jax.Array,        # (M, F) source features, M % TM == 0
    n_dst_blocks: int,
    tn: int = 128,
    tm: int = 128,
    tf: int = 128,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = default_interpret()
    return _block_spmm(rows, cols, blocks, x, n_dst_blocks, tn, tm, tf,
                       interpret)
