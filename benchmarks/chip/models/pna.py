"""PNA, Principal Neighbourhood Aggregation (Corso et al. 2020,
arXiv:2004.05718), as PyG's ``examples/pna.py`` lays it out: an input
projection, ``n_layers`` message-passing layers of width ``d_hidden`` and
an output head.

Per layer, for the edges ``j -> i`` of the sampled block:

- message ``m_ij = ReLU(h_j·W_src + h_i·W_dst + b)``;
- aggregation: mean, max, min and std (``sqrt(relu(E[m²] − mean²) +
  1e-5)``) of ``m_ij`` over each destination, all 0 for a destination
  with no in-edge;
- scaling: each aggregate times 1, ``log(d+1)/δ`` and ``δ/max(log(d+1),
  0.01)``, with ``d`` the destination's in-degree in the block and ``δ``
  the mean ``log(d+1)`` over the training graph's nodes (``DELTA``);
- update: ``h_i' = h_i + LN(ReLU([h_i ‖ 12 blocks]·W_upd + b_upd))``.

The loss and AdamW are ``reference.py``'s. The forward takes no
configuration, so ``δ`` is the module constant ``DELTA`` and ``validate``
holds the configuration's ``model.delta`` to it; a program whose ``δ``
differs shows in the ``loss`` check. The program's switch is
``RunConfig(model="pna")``.
"""
from __future__ import annotations

import numpy as np

import reference
import work

KERNEL = "fanout_aggregate_kernel"
AGGREGATORS = ["mean", "max", "min", "std"]
SCALERS = ["identity", "amplification", "attenuation"]
# mean log(d+1) of the in-degrees of the benchmark's reddit fixture graph
# (fixtures.power_law_graph at sage-reddit's graph settings, seed 0:
# 11,646,632 edges, in-degrees 21 to 93); pna-reddit.json states its
# derivation
DELTA = 3.921910050827763
STD_EPS = 1e-5
LN_EPS = 1e-5
CHUNK = 1 << 20   # edges a pass of the reference's aggregation
# per edge and channel: the message's add of the destination projection,
# then sum, square, sum of squares, max and min; the backward pass needs
# as many again
AGG_OPS = 6


def validate(config: dict) -> None:
    """Refuse settings this module's reference does not implement."""
    m = config["model"]
    wants = (("aggregators", AGGREGATORS), ("scalers", SCALERS),
             ("delta", DELTA), ("dtype", "float32"),
             ("matmul_precision", "highest"))
    wrong = {k: m.get(k) for k, want in wants if m.get(k) != want}
    if m["n_layers"] != len(config["training"]["fanouts"]):
        wrong["n_layers"] = m["n_layers"]
    if wrong:
        raise ValueError(f"pna: not implemented: {wrong} (aggregators "
                         f"{AGGREGATORS}, scalers {SCALERS}, delta {DELTA}, "
                         "float32 at highest, one fan-out a layer)")


def program_options(config: dict) -> dict:
    """The program's measured lane runs PNA with ``model="pna"``."""
    return {"model": "pna"}


def _shapes(config: dict) -> dict:
    """The weights of the program's ``pna.init`` tree, by path."""
    m, g = config["model"], config["graph"]
    d, f, c = m["d_hidden"], g["n_feat"], g["n_classes"]
    d_upd = d + len(AGGREGATORS) * len(SCALERS) * d
    layer = {"w_msg_src": (d, d), "w_msg_dst": (d, d), "b_msg": (d,),
             "w_upd": (d_upd, d), "b_upd": (d,), "ln_g": (d,), "ln_b": (d,)}
    out = {"w_in": (f, d), "b_in": (d,), "w_out": (d, c), "b_out": (c,)}
    for i in range(m["n_layers"]):
        out[f"layer_{i}"] = dict(layer)
    return out


def init_params(seed: int, config: dict) -> dict:
    """Glorot-uniform weights, zero biases, LayerNorm gain 1 and bias 0,
    made on the device in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    shapes = _shapes(config)

    def leaf(key, name, shape):
        if name == "ln_g":
            return jnp.ones(shape, jnp.float32)
        if len(shape) == 1:
            return jnp.zeros(shape, jnp.float32)
        bound = np.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    def make(key):
        params = {}
        for name in sorted(shapes):
            key, sub = jax.random.split(key)
            if isinstance(shapes[name], dict):
                lp = {}
                for k in sorted(shapes[name]):
                    sub, kk = jax.random.split(sub)
                    lp[k] = leaf(kk, k, shapes[name][k])
                params[name] = lp
            else:
                params[name] = leaf(sub, name, shapes[name])
        return params

    return jax.jit(make)(reference.key(seed))


def _layer_norm(x, g, b):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return g * (x - mu) / jnp.sqrt(var + LN_EPS) + b


def forward(params, x, blocks, control: bool = False):
    """Logits of the batch's padded destination rows: per-edge gathers of
    ``h[src]`` and ``h[dst]``, segment sums, maxima and minima, float32 at
    ``HIGHEST`` (``control``: every matmul and the aggregation's inputs at
    three bf16 passes).

    A layer's edges pass ``CHUNK`` at a time, and each pass's per-edge
    tensors are recomputed for the backward pass rather than kept: at
    reddit's size the input layer's ~4.2M padded edges, all at once,
    overflow one v5e's 16 GB. The passes' sums add up and their extrema
    combine, so the result is the whole layer's."""
    import jax
    import jax.numpy as jnp

    dot = reference.dot_3pass if control else reference.dot_highest

    def layer(lp, h, b):
        rows = b["dst_pos"].shape[0]
        h_dst = h[b["dst_pos"]]

        def chunk(acc, edges):
            src, dst = edges
            msg = jax.nn.relu(dot(h[src], lp["w_msg_src"])
                              + dot(h_dst[dst], lp["w_msg_dst"])
                              + lp["b_msg"])
            if control:
                msg = reference.round_3pass(msg)
            s, sq, mx, mn, deg = acc
            return (s + jax.ops.segment_sum(msg, dst, num_segments=rows),
                    sq + jax.ops.segment_sum(msg * msg, dst,
                                             num_segments=rows),
                    jnp.maximum(mx, jax.ops.segment_max(
                        msg, dst, num_segments=rows)),
                    jnp.minimum(mn, jax.ops.segment_min(
                        msg, dst, num_segments=rows)),
                    deg + jax.ops.segment_sum(
                        jnp.ones_like(dst, jnp.float32), dst,
                        num_segments=rows)[:, None]), None

        n = max(b["src"].shape[0] // CHUNK, 1)
        zero = jnp.zeros_like(h_dst)
        acc = (zero, zero, zero - jnp.inf, zero + jnp.inf,
               jnp.zeros((rows, 1), jnp.float32))
        (s, sq, mx, mn, deg), _ = jax.lax.scan(
            jax.checkpoint(chunk), acc,
            (b["src"].reshape(n, -1), b["dst"].reshape(n, -1)))
        den = jnp.maximum(deg, 1.0)
        mean = s / den
        std = jnp.sqrt(jnp.maximum(sq / den - mean * mean, 0.0) + STD_EPS)
        has = deg > 0
        log_deg = jnp.log(deg + 1.0)
        scales = (1.0, log_deg / DELTA, DELTA / jnp.maximum(log_deg, 1e-2))
        z = [h_dst] + [jnp.where(has, a, 0.0) * s_
                       for a in (mean, mx, mn, std) for s_ in scales]
        upd = dot(jnp.concatenate(z, axis=-1), lp["w_upd"]) + lp["b_upd"]
        return h_dst + _layer_norm(jax.nn.relu(upd), lp["ln_g"], lp["ln_b"])

    h = dot(x, params["w_in"]) + params["b_in"]
    for i, b in enumerate(blocks):
        h = layer(params[f"layer_{i}"], h, b)
    return dot(h, params["w_out"]) + params["b_out"]


def model_flops(layers: list[dict], config: dict) -> float:
    """FLOPs one training step requires, from true node and edge counts.

    Matmuls count ``2·rows·in·out`` forward and as much again for their
    weight gradient and for their input gradient; only the input
    projection, whose input is the untrained feature table, needs no
    input gradient. The sources and destinations are projected once per
    node (``h·W`` gathered equals ``h`` gathered times ``W``); the update
    multiplies ``[h_i ‖ 12 blocks]`` by ``W_upd``; the head maps the
    seeds to the classes. Messages and aggregation count ``AGG_OPS`` per
    edge and channel, forward and backward. Elementwise work (ReLU,
    scalers, LayerNorm, the residual) is not counted.
    """
    m, g = config["model"], config["graph"]
    d, f, c = m["d_hidden"], g["n_feat"], g["n_classes"]
    d_upd = d + len(AGGREGATORS) * len(SCALERS) * d
    total = 2.0 * 2.0 * layers[0]["n_src"] * f * d
    for lay in layers:
        proj = 2.0 * (lay["n_src"] + lay["n_dst"]) * d * d
        upd = 2.0 * lay["n_dst"] * d_upd * d
        total += 3.0 * (proj + upd) + 2.0 * AGG_OPS * lay["n_edges"] * d
    return total + 3.0 * 2.0 * layers[-1]["n_dst"] * d * c


def kernel_calls(layers: list[dict], config: dict) -> dict:
    """The forward message-and-aggregate of each layer, with FLOPs and
    bytes each, under the program's kernel name.

    The least bytes read each source and destination projection once and
    the neighbour table (one index an edge) once, and write the four
    aggregates of each destination once."""
    d = config["model"]["d_hidden"]
    calls = []
    for i, lay in enumerate(layers):
        nbytes = ((lay["n_src"] + 5 * lay["n_dst"]) * d * work.F32
                  + lay["n_edges"] * work.INDEX)
        calls.append({"layer": i, "pass": "forward",
                      "flops": float(AGG_OPS * lay["n_edges"] * d),
                      "bytes": float(nbytes)})
    return {KERNEL: calls}
