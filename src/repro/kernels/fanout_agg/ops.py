"""Public fixed-fan-out aggregation: the host neighbour table, and the
message-and-aggregate op with its gradient.

``fanout_aggregate(p_src, p_dst, nbr, deg)`` gives, per destination row,
the mean, max, min and std of the messages ``relu(p_src[nbr[i, k]] +
p_dst[i])`` over its real slots ``k < deg[i]``. The projections are taken
before the gather (``gather(h)·W = gather(h·W)`` row for row), so the
caller multiplies once per node, not once per edge. ``impl="pallas"``
runs the forward as ``fanout_aggregate_kernel`` over the XLA-gathered
slots; ``impl="xla"`` runs the same arithmetic as plain XLA (the path
where Pallas can only interpret).

The backward pass recomputes the messages rather than keeping ``E × d``
activations alive: ``mean`` passes ``g/deg``; ``std`` passes
``g·(m−μ)/(deg·σ)``, halved where ``E[m²]−μ²`` is exactly 0 and 0 where
it was clipped below 0 (the subgradient of ``jnp.maximum``); ``max`` and
``min`` split their gradient equally among tied slots, as JAX's
``reduce_max`` and ``segment_max`` do (with sampling with replacement,
duplicate neighbours give exactly tied messages). ``dp_src`` is a
scatter-add over ``nbr``; ``dp_dst`` a sum over the slots.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fanout_agg.kernel import (
    ROWS, STD_EPS, fanout_aggregate_kernel, finish, slot_sums,
)
from repro.kernels.segment_mm.kernel import default_interpret

LANES = 128


def neighbour_table(edge_src, edge_dst, edge_mask, n_dst: int):
    """The ELL layout of a block's kept edges on the host:
    ``(nbr (n_dst, fan) int32, deg (n_dst,) float32)``.

    Row ``i`` holds destination ``i``'s sources in edge order, its real
    slots first; ``fan`` is the largest in-degree (the layer's fan-out,
    at least 1). Padding slots point at row 0 and are outside ``deg``.
    """
    keep = np.asarray(edge_mask, bool)
    src = np.asarray(edge_src)[keep]
    dst = np.asarray(edge_dst)[keep]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    deg = np.bincount(dst, minlength=n_dst)
    fan = max(int(deg.max()) if len(deg) else 0, 1)
    start = np.cumsum(deg) - deg
    nbr = np.zeros((n_dst, fan), np.int32)
    nbr[dst, np.arange(len(dst)) - start[dst]] = src
    return nbr, deg.astype(np.float32)


def _forward(p_src, p_dst, nbr, deg, impl, interpret):
    fan = nbr.shape[1]
    dc = deg[:, None]
    if impl == "pallas":
        # lanes padded to 128 with zero columns; destinations to a whole
        # number of the kernel's row blocks with rows that have no slot
        (n, d), step = p_dst.shape, min(ROWS, -(-p_dst.shape[0] // 8) * 8)
        cols = (0, -(-d // LANES) * LANES - d)
        rows = (0, -(-n // step) * step - n)
        g = jnp.pad(p_src, ((0, 0), cols))[jnp.pad(nbr, (rows, (0, 0))).T]
        out = fanout_aggregate_kernel(g, jnp.pad(p_dst, (rows, cols)),
                                      jnp.pad(dc, (rows, (0, 0))),
                                      interpret=interpret)
        return tuple(out[j, :n, :d] for j in range(4))
    g = p_src[nbr.T]
    return finish(*slot_sums(lambda k: g[k], fan, p_dst, dc), dc)[:4]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _aggregate(p_src, p_dst, nbr, deg, impl, interpret):
    return _forward(p_src, p_dst, nbr, deg, impl, interpret)


def _aggregate_fwd(p_src, p_dst, nbr, deg, impl, interpret):
    out = _forward(p_src, p_dst, nbr, deg, impl, interpret)
    return out, (p_src, p_dst, nbr, deg)


def _aggregate_bwd(impl, interpret, res, cts):
    p_src, p_dst, nbr, deg = res
    fan = nbr.shape[1]
    dc = deg[:, None]
    g = p_src[nbr.T]                                  # (fan, N, d)
    mean, mx, mn, _, var = finish(
        *slot_sums(lambda k: g[k], fan, p_dst, dc), dc)
    std = jnp.sqrt(jnp.maximum(var, 0.0) + STD_EPS)
    has = dc > 0
    den = jnp.maximum(dc, 1.0)
    c_mean, c_max, c_min, c_std = (jnp.where(has, c, 0.0) for c in cts)
    clip = jnp.where(var > 0, 1.0, jnp.where(var == 0, 0.5, 0.0))
    c_std = c_std * clip / (den * std)
    pre = g + p_dst
    m = jnp.maximum(pre, 0.0)
    valid = jnp.arange(fan)[:, None, None] < dc       # (fan, N, 1)
    at_max = valid & (m == mx)
    at_min = valid & (m == mn)
    n_max = jnp.maximum(at_max.sum(0), 1)
    n_min = jnp.maximum(at_min.sum(0), 1)
    d_m = (c_mean / den + jnp.where(at_max, c_max / n_max, 0.0)
           + jnp.where(at_min, c_min / n_min, 0.0) + c_std * (m - mean))
    d_pre = jnp.where(valid & (pre > 0), d_m, 0.0)
    d_src = jnp.zeros_like(p_src).at[nbr.T].add(d_pre)
    return d_src, d_pre.sum(0), None, None


_aggregate.defvjp(_aggregate_fwd, _aggregate_bwd)


def fanout_aggregate(p_src, p_dst, nbr, deg, impl: str = "xla",
                     interpret: bool | None = None):
    """``(mean, max, min, std)``, each ``(N, d)``, of each destination's
    messages ``relu(p_src[nbr[i, k]] + p_dst[i])`` over ``k < deg[i]``.

    ``p_src`` ``(S, d)``, ``p_dst`` ``(N, d)``, ``nbr`` ``(N, fan)`` int32
    rows of ``p_src``, ``deg`` ``(N,)`` float32 (``neighbour_table``).
    ``interpret=None`` asks the backend (the Pallas interpreter on CPU).
    """
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    if interpret is None:
        interpret = default_interpret()
    return _aggregate(p_src, p_dst, nbr, deg, impl, interpret)
