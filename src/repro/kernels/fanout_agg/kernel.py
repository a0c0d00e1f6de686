"""Fixed-fan-out neighbour aggregation Pallas kernel: PNA's message and its
four aggregators in one pass.

The sampler draws exactly ``fan`` in-neighbours per destination, with
replacement (none where a node has no in-neighbour), so a layer's edges
are a dense neighbour table ``nbr[n_dst, fan]`` of local source rows: an
ELL layout, not a scatter. The caller gathers the source projections by
that table slot-major, ``g[k, i] = P_src[nbr[i, k]]``, and over a block
of destination rows the kernel forms each slot's message
``relu(g[k] + P_dst)`` and reduces the slots into mean, max, min and std.
Slot ``k`` of destination ``i`` is real where ``k < deg[i]``; a
destination with no real slot gets 0 for all four aggregates.

The feature axis is a multiple of 128 lanes (the caller pads it with zero
columns and slices them off); nothing here counts across columns, so
padded columns reach no real one.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 512     # destination rows per grid step
STD_EPS = 1e-5  # inside std's square root, as models/gnn/common.scatter_std


def slot_sums(slot, fan: int, p_dst, deg):
    """Sum, sum of squares, max and min of the messages
    ``relu(slot(k) + p_dst)`` over the real slots ``k < deg``, slot by slot
    in order. ``deg`` broadcasts against ``p_dst`` (a column of counts).
    Slot 0 seeds max and min whether or not it is real: rows without a
    real slot are masked by ``finish``."""
    m = jnp.maximum(slot(0) + p_dst, 0.0)
    s, sq, mx, mn = m, m * m, m, m
    zero = jnp.zeros_like(m)
    first = deg > 0
    s, sq = jnp.where(first, s, zero), jnp.where(first, sq, zero)
    for k in range(1, fan):
        m = jnp.maximum(slot(k) + p_dst, 0.0)
        valid = deg > k
        s = s + jnp.where(valid, m, zero)
        sq = sq + jnp.where(valid, m * m, zero)
        mx = jnp.where(valid, jnp.maximum(mx, m), mx)
        mn = jnp.where(valid, jnp.minimum(mn, m), mn)
    return s, sq, mx, mn


def finish(s, sq, mx, mn, deg):
    """``(mean, max, min, std, var)`` from ``slot_sums``; the four
    aggregates are 0 where ``deg`` is 0, ``var`` (E[m²] − mean², before
    std clips it at 0) is left unmasked."""
    den = jnp.maximum(deg, 1.0)
    mean = s / den
    var = sq / den - mean * mean
    std = jnp.sqrt(jnp.maximum(var, 0.0) + STD_EPS)
    has = deg > 0
    zero = jnp.zeros_like(mean)
    return tuple(jnp.where(has, a, zero) for a in (mean, mx, mn, std)) + (
        var,)


def _kernel(fan, g_ref, p_ref, deg_ref, o_ref):
    deg = deg_ref[...]
    sums = slot_sums(lambda k: g_ref[k], fan, p_ref[...], deg)
    for j, a in enumerate(finish(*sums, deg)[:4]):
        o_ref[j] = a.astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("interpret",))
def fanout_aggregate_kernel(
    g: jax.Array,       # (fan, N, D) gathered source projections, slot-major
    p_dst: jax.Array,   # (N, D) destination projections, bias included
    deg: jax.Array,     # (N, 1) float32 real slots per destination
    interpret: bool = False,
):
    """``(4, N, D)``: mean, max, min and std of each destination's
    messages. ``D % 128 == 0``; ``N`` is a multiple of ``ROWS`` or smaller
    than it (and then a multiple of 8)."""
    fan, n, d = g.shape
    rows = min(ROWS, n)
    assert d % 128 == 0 and n % rows == 0 and rows % 8 == 0
    return pl.pallas_call(
        partial(_kernel, fan),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((fan, rows, d), lambda i: (0, i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((4, rows, d), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, n, d), g.dtype),
        interpret=interpret,
        name="fanout_aggregate_kernel",  # a stable name in the trace
    )(g, p_dst, deg)
