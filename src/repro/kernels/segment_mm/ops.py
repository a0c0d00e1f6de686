"""Public SpMM ops: edge-list -> block-sparse conversion + kernel dispatch.

The conversion is split in two: ``block_sparse_plan`` computes, on the
host, the tile ids and each edge's tile slot and in-tile offset; the
tiles are then scattered from that plan, by numpy (``to_block_sparse``)
or on the device inside a compiled step (``tiles_from_plan``), which
uploads a few bytes per edge in place of 64 KiB per tile.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from functools import partial

from repro.kernels.segment_mm.kernel import block_spmm_kernel, default_interpret


def block_sparse_plan(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    n_dst: int,
    n_src: int,
    tn: int = 128,
    tm: int = 128,
):
    """Index plan of the block-sparse format of an edge list.

    The tiles are the distinct (dst row-block, src col-block) pairs of the
    edges, sorted by row then col, plus a zero tile (col 0) for every dst
    row-block no edge reaches, so the kernel writes the full output.
    Returns ``(rows (nb,), cols (nb,), slot (E,), off (E,), n_dst_blocks,
    n_src_pad)``: each edge adds its weight at ``off = (dst % tn) * tm +
    src % tm`` of the flattened tile ``slot``.
    """
    n_dst_blocks = -(-n_dst // tn)
    n_src_blocks = -(-n_src // tm)
    br = edge_dst // tn
    bc = edge_src // tm
    key = br.astype(np.int64) * n_src_blocks + bc
    uniq, inv = np.unique(key, return_inverse=True)
    rows = (uniq // n_src_blocks).astype(np.int32)
    cols = (uniq % n_src_blocks).astype(np.int32)
    # `uniq` is sorted by (row, col) already, so each tile's final
    # row-sorted position is computed instead of concatenating the zero
    # tiles and re-sorting: real tile i shifts right past every missing
    # row before it; missing row m lands after all real tiles with row < m
    # plus earlier missings
    present = np.zeros(n_dst_blocks, bool)
    present[rows] = True
    missing = np.flatnonzero(~present).astype(np.int32)
    nb = len(uniq) + len(missing)
    pos_real = np.arange(len(uniq)) + np.searchsorted(missing, rows)
    pos_missing = np.searchsorted(rows, missing) + np.arange(len(missing))
    rows_all = np.empty(nb, np.int32)
    cols_all = np.zeros(nb, np.int32)
    rows_all[pos_real] = rows
    rows_all[pos_missing] = missing
    cols_all[pos_real] = cols
    off = (edge_dst % tn) * tm + edge_src % tm
    return (rows_all, cols_all, pos_real[inv], off, n_dst_blocks,
            n_src_blocks * tm)


def to_block_sparse(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    n_dst: int,
    n_src: int,
    tn: int = 128,
    tm: int = 128,
    edge_weight: np.ndarray | None = None,
):
    """Convert an edge list into row-sorted dense adjacency blocks on the
    host: ``block_sparse_plan`` scattered by numpy.

    Returns (rows (nb,), cols (nb,), blocks (nb, tn, tm), n_dst_blocks,
    n_src_pad).
    """
    rows, cols, slot, off, n_dst_blocks, n_src_pad = block_sparse_plan(
        edge_src, edge_dst, n_dst, n_src, tn, tm
    )
    w = (
        edge_weight.astype(np.float32)
        if edge_weight is not None
        else np.ones(len(edge_src), np.float32)
    )
    # the zero tiles are never written (calloc pages stay zero)
    blocks = np.zeros((len(rows), tn, tm), np.float32)
    np.add.at(blocks.reshape(len(rows), tn * tm), (slot, off), w)
    return rows, cols, blocks, n_dst_blocks, n_src_pad


def sorted_edge_slots(slot, off, keep, length: int, n_tiles: int,
                      tile_size: int):
    """The kept edges' ``(slot, off)`` as int32, sorted by slot then
    offset and padded to ``length`` with ``slot = n_tiles`` and the last
    offset, which ``tiles_from_plan`` scatters as zeros."""
    key = np.sort(slot[keep].astype(np.int64) * tile_size + off[keep])
    slot_out = np.full(length, n_tiles, np.int32)
    off_out = np.full(length, tile_size - 1, np.int32)
    slot_out[: len(key)] = key // tile_size
    off_out[: len(key)] = key % tile_size
    return slot_out, off_out


def tiles_from_plan(slot, off, n_tiles: int, tn: int = 128, tm: int = 128):
    """The ``(n_tiles, tn, tm)`` float32 tiles built on the device: 1.0
    added at ``off`` of tile ``slot`` for each edge, as ``sorted_edge_slots``
    lays them out. Sums of 1.0 are exact in any order, so the tiles equal
    ``to_block_sparse``'s for 0/1 weights bit for bit.

    A padding edge (``slot = n_tiles``) adds 0.0 to the last entry of the
    last tile instead of being dropped: that index is in bounds and comes
    last, so the indices stay in the sorted order the scatter is promised.
    XLA's TPU backend rewrites a dropped index to -1, which breaks that
    order, and the sorted scatter then lost updates on a TPU v5e.
    """
    pad = slot >= n_tiles
    return jnp.zeros((n_tiles, tn, tm), jnp.float32).at[
        jnp.where(pad, n_tiles - 1, slot), off // tm, off % tm
    ].add(jnp.where(pad, 0.0, 1.0), mode="promise_in_bounds",
          indices_are_sorted=True)


def block_spmm(rows, cols, blocks, x, n_dst_blocks, tn=128, tm=128, tf=128,
               interpret=None):
    """Pallas-kernel executor; ``interpret=None`` auto-detects the backend."""
    return block_spmm_kernel(
        jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(blocks),
        x, n_dst_blocks, tn=tn, tm=tm, tf=tf, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("n_dst_blocks", "tn", "tm"))
def block_spmm_xla(rows, cols, blocks, x, n_dst_blocks, tn=128, tm=128):
    """Compiled XLA executor of the same block-sparse format.

    Same math as the Pallas kernel — per-block dense matmul accumulated by
    destination row-block — expressed as a batched matmul + segment-sum so
    it compiles on any backend. This is the hot-path implementation where
    Pallas can only interpret (CPU); ``segment_sum`` zero-fills row-blocks
    with no incoming blocks, so zero padding blocks are tolerated but not
    required.
    """
    xb = x.reshape(-1, tm, x.shape[1])                  # (n_src_blocks, TM, F)
    prod = jnp.matmul(
        blocks, xb[cols], preferred_element_type=jnp.float32
    )                                                   # (nb, TN, F)
    y = jax.ops.segment_sum(prod, rows, num_segments=n_dst_blocks)
    return y.reshape(n_dst_blocks * tn, x.shape[1]).astype(x.dtype)


def segment_mm(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    x: jax.Array,
    n_dst: int,
    edge_weight: np.ndarray | None = None,
    tn: int = 128,
    tm: int = 128,
    tf: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """End-to-end: edge list -> block-sparse -> Pallas SpMM -> (n_dst, F)."""
    n_src = x.shape[0]
    rows, cols, blocks, n_dst_blocks, n_src_pad = to_block_sparse(
        np.asarray(edge_src), np.asarray(edge_dst), n_dst, n_src, tn, tm,
        edge_weight,
    )
    f = x.shape[1]
    f_pad = -(-f // tf) * tf
    x_pad = jnp.zeros((n_src_pad, f_pad), x.dtype)
    x_pad = x_pad.at[:n_src, :f].set(x)
    out = block_spmm(rows, cols, blocks, x_pad, n_dst_blocks,
                     tn=tn, tm=tm, tf=tf, interpret=interpret)
    return out[:n_dst, :f]
