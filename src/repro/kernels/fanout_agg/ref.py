"""Pure-jnp oracle for the fixed-fan-out aggregation: per-edge messages
scattered into their destinations with segment ops."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.gnn import common


def fanout_aggregate_ref(p_src, p_dst, edge_src, edge_dst, n_dst: int):
    """``(mean, max, min, std)`` of ``relu(p_src[src] + p_dst[dst])`` over
    each destination's edges; 0 for a destination with none."""
    msg = jax.nn.relu(p_src[edge_src] + p_dst[edge_dst])
    deg = common.in_degrees(edge_dst, n_dst)
    has = (deg > 0)[:, None]
    aggs = (common.scatter_mean(msg, edge_dst, n_dst),
            common.scatter_max(msg, edge_dst, n_dst),
            common.scatter_min(msg, edge_dst, n_dst),
            common.scatter_std(msg, edge_dst, n_dst))
    return tuple(jnp.where(has, a, 0.0) for a in aggs)
