"""PNA — Principal Neighbourhood Aggregation (Corso et al. 2020).

Assigned config: 4 layers, d_hidden=75, aggregators mean/max/min/std,
scalers identity/amplification/attenuation. Around the layers, an input
projection ``h = x·W_in + b_in`` and an output head ``h·W_out + b_out``.
Each layer:

  m_ij   = ReLU(h_j·W_src + h_i·W_dst + b)    (message of edge j -> i)
  agg    = [mean, max, min, std] of m_ij      (4 aggregators; all 0 for
                                               a node with no in-edge)
  scaled = [1, log(d+1)/delta, delta/log(d+1)] x agg  (3 scalers -> 12 blocks)
  h_i'   = h_i + LN(ReLU([h_i || scaled]·W_upd + b_upd))

``d`` is the node's in-degree in the graph the layer runs over: the full
graph in ``apply_full``, the sampled block in ``apply_blocks``.

Two entry points:
  * ``apply_full``   — full-graph message passing over an edge list
  * ``apply_blocks`` — sampled mini-batch forward over sampler Blocks, the
    plain reference of the measured lane's PNA step
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import common
from repro.models.param import ParamBuilder

AGGREGATORS = ("mean", "max", "min", "std")
N_SCALERS = 3


@dataclasses.dataclass(frozen=True)
class PNAConfig:
    d_in: int
    d_hidden: int = 75
    n_classes: int = 47
    n_layers: int = 4
    delta: float = 2.5  # mean log-degree of the training graphs


def graph_delta(indptr: np.ndarray) -> float:
    """PNA's ``delta``: the mean of ``log(d + 1)`` over the nodes of a
    graph, ``d`` each node's in-degree from the in-neighbour CSR."""
    return float(np.mean(np.log(np.diff(np.asarray(indptr)) + 1.0)))


def init(key: jax.Array, cfg: PNAConfig, dtype=jnp.float32,
         abstract: bool = False):
    pb = ParamBuilder(key, dtype, abstract)
    pb.param("w_in", (cfg.d_in, cfg.d_hidden), ("gnn_in", "gnn_hidden"))
    pb.param("b_in", (cfg.d_hidden,), ("gnn_hidden",), init="zeros")
    d = cfg.d_hidden
    n_agg_out = len(AGGREGATORS) * N_SCALERS * d
    for i in range(cfg.n_layers):
        layer = pb.scope(f"layer_{i}")
        layer.param("w_msg_src", (d, d), ("gnn_hidden", "gnn_hidden"))
        layer.param("w_msg_dst", (d, d), ("gnn_hidden", "gnn_hidden"))
        layer.param("b_msg", (d,), ("gnn_hidden",), init="zeros")
        layer.param("w_upd", (d + n_agg_out, d), ("gnn_in", "gnn_hidden"))
        layer.param("b_upd", (d,), ("gnn_hidden",), init="zeros")
        layer.param("ln_g", (d,), ("gnn_hidden",), init="ones")
        layer.param("ln_b", (d,), ("gnn_hidden",), init="zeros")
    pb.param("w_out", (d, cfg.n_classes), ("gnn_hidden", "classes"))
    pb.param("b_out", (cfg.n_classes,), ("classes",), init="zeros")
    return pb.params, pb.axes


def update(lp, cfg: PNAConfig, h_dst, aggs, deg):
    """Scale the four aggregates by the destinations' in-degrees ``deg``
    and apply the residual update."""
    log_deg = jnp.log(deg + 1.0)[:, None]
    amp = log_deg / cfg.delta
    att = cfg.delta / jnp.maximum(log_deg, 1e-2)
    scaled = []
    for a in aggs:
        scaled.extend([a, a * amp, a * att])
    z = jnp.concatenate([h_dst] + scaled, axis=-1)
    upd = z @ lp["w_upd"] + lp["b_upd"]
    return h_dst + common.layer_norm(jax.nn.relu(upd), lp["ln_g"], lp["ln_b"])


def _layer(lp, cfg: PNAConfig, h_src, h_dst, edge_src, edge_dst, edge_mask):
    n_dst = h_dst.shape[0]
    msg = jax.nn.relu(
        h_src[edge_src] @ lp["w_msg_src"] + h_dst[edge_dst] @ lp["w_msg_dst"]
        + lp["b_msg"]
    )
    deg = common.in_degrees(edge_dst, n_dst, edge_mask)
    has = (deg > 0)[:, None]
    aggs = [
        common.scatter_mean(msg, edge_dst, n_dst, edge_mask),
        common.scatter_max(msg, edge_dst, n_dst, edge_mask),
        common.scatter_min(msg, edge_dst, n_dst, edge_mask),
        common.scatter_std(msg, edge_dst, n_dst, edge_mask),
    ]
    aggs = [jnp.where(has, a, 0.0) for a in aggs]
    return update(lp, cfg, h_dst, aggs, deg)


def apply_full(params, cfg: PNAConfig, x, edge_index, edge_mask=None):
    h = x @ params["w_in"] + params["b_in"]
    for i in range(cfg.n_layers):
        h = _layer(params[f"layer_{i}"], cfg, h, h, edge_index[0],
                   edge_index[1], edge_mask)
    return h @ params["w_out"] + params["b_out"]


def apply_blocks(params, cfg: PNAConfig, x_input, blocks):
    """Sampled forward. ``blocks`` is a list of dicts with jnp arrays
    edge_src, edge_dst, edge_mask, dst_pos (input layer first);
    ``x_input`` are the features of blocks[0]'s source nodes. Returns the
    logits of the last block's destinations."""
    h = x_input @ params["w_in"] + params["b_in"]
    for i, blk in enumerate(blocks):
        h = _layer(params[f"layer_{i}"], cfg, h, h[blk["dst_pos"]],
                   blk["edge_src"], blk["edge_dst"], blk["edge_mask"])
    return h @ params["w_out"] + params["b_out"]
