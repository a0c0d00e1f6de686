"""Row-gather Pallas kernel behind EmbeddingBag and the device tier.

JAX has no native EmbeddingBag. On TPU the lookup rows are gathered by
DMA: the table stays in HBM (``memory_space=pl.ANY``), the scalar-
prefetched index array names the rows, and each grid step copies
``ROWS_PER_STEP`` of them into a VMEM buffer that becomes one
(ROWS_PER_STEP, D) output block. A block of a single row would break the
TPU's (8, 128) tiling, so rows travel in groups of eight.

The table is laid out (R, 1, D) with D a multiple of 128: one row per
leading index, so a DMA of one row is a whole tile and needs no sublane
offset. Bag reduction and per-lookup weights, where a caller needs them,
are a segment sum over the gathered rows; the gather itself is exact, so
it is bit-equal to ``table[idx]``.
"""
from __future__ import annotations

from functools import partial

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS_PER_STEP = 8   # one (8, 128) sublane tile of output rows per grid step


def _gather_kernel(idx_ref, table_ref, o_ref, buf, sems):
    base = pl.program_id(0) * ROWS_PER_STEP
    copies = [
        pltpu.make_async_copy(
            table_ref.at[pl.ds(idx_ref[base + k], 1)],
            buf.at[pl.ds(k, 1)],
            sems.at[k],
        )
        for k in range(ROWS_PER_STEP)
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    o_ref[...] = buf[...].reshape(o_ref.shape)


@partial(jax.jit, static_argnames=("interpret",))
def gather_rows_kernel(
    indices: jax.Array,   # (L,) int32, L % ROWS_PER_STEP == 0
    table: jax.Array,     # (R, 1, D), D % 128 == 0
    interpret: bool = False,
):
    """``table[indices, 0]`` as an (L, D) array."""
    l = indices.shape[0]
    _, one, d = table.shape
    assert one == 1 and l % ROWS_PER_STEP == 0
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(l // ROWS_PER_STEP,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((ROWS_PER_STEP, d), lambda g, idx: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((ROWS_PER_STEP, 1, d), table.dtype),
                pltpu.SemaphoreType.DMA((ROWS_PER_STEP,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((l, d), table.dtype),
        interpret=interpret,
        name="gather_rows_kernel",  # a stable name in the profiler trace
    )(indices, table)
