"""Public row-gather ops: table layout and index padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.kernel import ROWS_PER_STEP, gather_rows_kernel
from repro.kernels.segment_mm.kernel import default_interpret

LANES = 128


def gather_layout(table) -> jax.Array:
    """(R, D) rows -> the kernel's (R, 1, D_pad) layout, D_pad % 128 == 0.

    Build the device table in this layout once; ``gather_rows`` then
    reads it in place.
    """
    table = jnp.asarray(table)
    d = table.shape[1]
    table = jnp.pad(table, ((0, 0), (0, -(-d // LANES) * LANES - d)))
    return table[:, None, :]


def gather_rows(table: jax.Array, indices, n_feat: int,
                interpret: bool | None = None) -> jax.Array:
    """Exact row gather ``table[indices, 0, :n_feat]`` from a table in
    :func:`gather_layout`; the index count is padded to the kernel's step."""
    if interpret is None:
        interpret = default_interpret()
    indices = jnp.asarray(indices, jnp.int32)
    n = indices.shape[0]
    n_pad = -(-n // ROWS_PER_STEP) * ROWS_PER_STEP
    if n_pad != n:
        indices = jnp.pad(indices, (0, n_pad - n))
    out = gather_rows_kernel(indices, table, interpret=bool(interpret))
    return out[:n, :n_feat]
