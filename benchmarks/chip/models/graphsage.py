"""GraphSAGE (Hamilton et al. 2017) with the mean aggregator, as the DGL
distributed example trains it.

Per layer ``h_dst @ W_self + mean_{u->v} h_u @ W_neigh + b``, ReLU between
layers; the loss and AdamW are ``reference.py``'s. The widths come from
the configuration: ``[n_feat] + [d_hidden] * (n_layers - 1) +
[n_classes]``. The program's default model, so it needs no option to be
selected.
"""
from __future__ import annotations

import numpy as np

import reference
import work

SPMM = "block_spmm_kernel"


def _dims(config: dict) -> list[int]:
    m, g = config["model"], config["graph"]
    return ([g["n_feat"]] + [m["d_hidden"]] * (m["n_layers"] - 1)
            + [g["n_classes"]])


def validate(config: dict) -> None:
    """Refuse settings this module's reference does not implement."""
    m = config["model"]
    wrong = {k: m.get(k) for k, want in (("aggregator", "mean"),
                                         ("dtype", "float32"),
                                         ("matmul_precision", "highest"))
             if m.get(k) != want}
    if m["n_layers"] != len(config["training"]["fanouts"]):
        wrong["n_layers"] = m["n_layers"]
    if wrong:
        raise ValueError(f"graphsage: not implemented: {wrong} (mean "
                         "aggregator, float32 at highest, one fan-out a "
                         "layer)")


def program_options(config: dict) -> dict:
    """The program's measured lane runs this model by default."""
    return {}


def init_params(seed: int, config: dict) -> dict:
    """Glorot-uniform weights (ReLU gain, as DGL's ``SAGEConv``) and zero
    biases, made on the device in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    dims = _dims(config)

    def make(key):
        params = {}
        for i in range(len(dims) - 1):
            fi, fo = dims[i], dims[i + 1]
            bound = np.sqrt(2.0) * np.sqrt(6.0 / (fi + fo))
            k1, k2, key = jax.random.split(key, 3)
            params[f"layer_{i}"] = {
                "w_self": jax.random.uniform(k1, (fi, fo), jnp.float32,
                                             -bound, bound),
                "w_neigh": jax.random.uniform(k2, (fi, fo), jnp.float32,
                                              -bound, bound),
                "b": jnp.zeros((fo,), jnp.float32),
            }
        return params

    return jax.jit(make)(reference.key(seed))


def forward(params, x, blocks, control: bool = False):
    """Logits of the batch's padded destination rows; per-edge gathers and
    a segment sum, float32 at ``HIGHEST`` (``control``: three bf16
    passes)."""
    import jax
    import jax.numpy as jnp

    dot = reference.dot_3pass if control else reference.dot_highest
    h = x
    for i, b in enumerate(blocks):
        lp = params[f"layer_{i}"]
        src_h = reference.round_3pass(h) if control else h
        rows = b["dst_pos"].shape[0]
        msg = src_h[b["src"]]
        summed = jax.ops.segment_sum(msg, b["dst"], num_segments=rows)
        count = jax.ops.segment_sum(jnp.ones_like(b["dst"], jnp.float32),
                                    b["dst"], num_segments=rows)
        agg = summed / jnp.maximum(count, 1.0)[:, None]
        h_new = dot(h[b["dst_pos"]], lp["w_self"]) + dot(agg, lp["w_neigh"])
        h_new = h_new + lp["b"]
        h = jax.nn.relu(h_new) if i < len(blocks) - 1 else h_new
    return h


def model_flops(layers: list[dict], config: dict) -> float:
    """FLOPs one training step requires (mean aggregator, 2 weights).

    Per layer with input width ``fi`` and output width ``fo``: aggregation
    ``2·E·fi``, the two projections ``2·(2·n_dst·fi·fo)`` forward and as
    much again for their weight gradients. Every layer but the first also
    needs the gradient of its input: the projections' input gradients and
    the transposed aggregation. Layer 0's input is the feature table, which
    is not trained, so it needs none. Elementwise work is not counted.
    """
    dims = _dims(config)
    total = 0.0
    for i, lay in enumerate(layers):
        fi, fo = dims[i], dims[i + 1]
        agg = 2.0 * lay["n_edges"] * fi
        proj = 2.0 * 2.0 * lay["n_dst"] * fi * fo
        total += agg + proj + proj            # forward, weight gradients
        if i > 0:
            total += proj + agg                # input gradients
    return total


def kernel_calls(layers: list[dict], config: dict) -> dict:
    """The sparse aggregations one step needs, with FLOPs and bytes each,
    under the program's kernel name.

    Forward at every layer; transposed at every layer but the first. The
    least bytes read each source row once, write each destination row
    once and read the edge list (source and destination index) once.
    """
    dims = _dims(config)
    calls = []
    for i, lay in enumerate(layers):
        f = dims[i]
        flops = 2.0 * lay["n_edges"] * f
        nbytes = ((lay["n_src"] + lay["n_dst"]) * f * work.F32
                  + 2 * lay["n_edges"] * work.INDEX)
        calls.append({"layer": i, "pass": "forward", "flops": flops,
                      "bytes": nbytes})
        if i > 0:
            calls.append({"layer": i, "pass": "transposed", "flops": flops,
                          "bytes": nbytes})
    return {SPMM: calls}
