"""Plain reference of the timed training step, and the comparison with it.

What any model shares: the sampled blocks checked against the fixture
graph, a batch as the reference reads it (local edge lists per layer,
input rows from the plain feature table), softmax cross entropy over the
batch's seeds, AdamW, and the comparison with the program's observations.
The model itself, its weights and its forward, is ``models/<arch>.py``'s,
which the configuration names; ``loss_fn`` and ``train`` take its
``forward``. Written in straightforward ``jax.numpy`` float32 at
``HIGHEST`` matmul precision: no kernel, tile, cache or bucket of the
program. It imports nothing of the program and takes nothing it made: the
weights come from the model module's ``init_params`` (the benchmark hands
the same ones to the program), features and labels from the benchmark's
own fixture.

The control is the reference with every matmul and the aggregation's
inputs at three bf16 passes (``high``), the step below the ``highest``
that the configuration states: ``dot_3pass`` and ``round_3pass``, which a
model's forward uses when called with ``control``; they are written out
here so that they mean the same on every backend.
"""
from __future__ import annotations

import numpy as np

# relative to the larger of the leaf's own norm and the median leaf's;
# leaves whose first reference gradient is under this share of the median
# leaf's move under Adam by round-off alone and are not compared
NEGLIGIBLE_GRAD = 1e-3


def key(seed: int):
    """The PRNG key of ``seed``, any whole number up to 64 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)


def dot_highest(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _split(a):
    import jax.numpy as jnp

    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot_3pass(a, b):
    import jax.numpy as jnp

    (ah, al), (bh, bl) = _split(a), _split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + d(ah, bl) + d(al, bh)


def round_3pass(x):
    import jax.numpy as jnp

    hi, lo = _split(x)
    return hi.astype(jnp.float32) + lo.astype(jnp.float32)


def _bucket(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def edge_keys(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Every edge ``u -> v`` of the fixture's in-neighbour CSR as the key
    ``v * n + u``, sorted."""
    n = len(indptr) - 1
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    return np.sort(dst * n + indices.astype(np.int64))


def block_faults(mb, indptr: np.ndarray, in_edges: np.ndarray,
                 fanouts) -> int:
    """Faults of one sampled batch against the fixture graph.

    Counted per entry: a sampled edge that is not an edge of the graph (or
    whose ends are no real node of its block); a destination that does not
    draw exactly its layer's fan-out of in-neighbours (none, where it has
    no in-neighbour), sampled with replacement; a destination whose
    ``dst_pos`` is not its own row in the source list; a layer whose
    sources are not the destinations of the layer below, in order; input
    rows that are not the first layer's sources; a seed that repeats.
    ``fanouts`` are listed from the output layer inward, as the
    configuration states them (``in_edges``: ``edge_keys``).
    """
    n = len(indptr) - 1
    blocks, faults = mb.blocks, 0
    for i, b in enumerate(blocks):
        fan = fanouts[len(blocks) - 1 - i]
        src = np.asarray(b.src_nodes)[np.asarray(b.src_mask, bool)]
        dst = np.asarray(b.dst_nodes)[np.asarray(b.dst_mask, bool)]
        pos = np.asarray(b.dst_pos)[: len(dst)]
        own = (pos >= 0) & (pos < len(src))
        faults += int(np.count_nonzero(~own))
        faults += int(np.count_nonzero(src[pos[own]] != dst[own]))
        m = np.asarray(b.edge_mask, bool)
        es = np.asarray(b.edge_src)[m].astype(np.int64)
        ed = np.asarray(b.edge_dst)[m].astype(np.int64)
        real = (es >= 0) & (es < len(src)) & (ed >= 0) & (ed < len(dst))
        faults += int(np.count_nonzero(~real))
        es, ed = es[real], ed[real]
        keys = dst[ed].astype(np.int64) * n + src[es].astype(np.int64)
        at = np.minimum(np.searchsorted(in_edges, keys), len(in_edges) - 1)
        faults += int(np.count_nonzero(in_edges[at] != keys))
        deg = indptr[dst + 1] - indptr[dst]
        drawn = np.bincount(ed, minlength=len(dst))
        faults += int(np.count_nonzero(drawn != np.where(deg > 0, fan, 0)))
        if i + 1 < len(blocks):
            nxt = blocks[i + 1]
            above = np.asarray(nxt.src_nodes)[np.asarray(nxt.src_mask, bool)]
            k = min(len(above), len(dst))
            faults += abs(len(above) - len(dst))
            faults += int(np.count_nonzero(above[:k] != dst[:k]))
        else:
            faults += len(dst) - len(np.unique(dst))
    first = np.asarray(blocks[0].src_nodes)
    inputs = np.asarray(mb.input_nodes)
    k = min(len(first), len(inputs))
    faults += abs(len(first) - len(inputs))
    faults += int(np.count_nonzero(first[:k] != inputs[:k]))
    return faults


def batch_arrays(mb, features: np.ndarray, labels: np.ndarray) -> dict:
    """The batch as the reference reads it: local edge lists per layer,
    input rows from the plain feature table, labels of the seeds.

    Sizes are padded to powers of two, so that the jitted reference
    compiles once per size class: padded input rows are zero, padded
    edges run from row 0 into a spare destination row past the real ones,
    padded destinations carry no label weight. Real rows compute exactly
    what they would unpadded.
    """
    blocks = []
    for b in mb.blocks:
        m = np.asarray(b.edge_mask, bool)
        src = np.asarray(b.edge_src)[m].astype(np.int32)
        dst = np.asarray(b.edge_dst)[m].astype(np.int32)
        n_dst = int(len(b.dst_nodes))
        n_edges = _bucket(len(src))
        rows = _bucket(n_dst + 1)
        blocks.append({
            "src": np.pad(src, (0, n_edges - len(src))),
            "dst": np.pad(dst, (0, n_edges - len(dst)),
                          constant_values=n_dst),
            "dst_pos": np.pad(np.asarray(b.dst_pos).astype(np.int32),
                              (0, rows - n_dst)),
        })
    ids = np.asarray(mb.input_nodes, np.int64)
    x = np.zeros((_bucket(len(ids)), features.shape[1]), np.float32)
    x[: len(ids)] = features[ids]
    last = mb.blocks[-1]
    rows = len(blocks[-1]["dst_pos"])
    lab = labels[np.asarray(last.dst_nodes)].astype(np.int32)
    mask = np.asarray(last.dst_mask, np.float32)
    return {"x": x, "n_input": len(ids), "blocks": blocks,
            "labels": np.pad(lab, (0, rows - len(lab))),
            "mask": np.pad(mask, (0, rows - len(mask)))}


def on_device(batch: dict) -> dict:
    """``batch``'s arrays on the device."""
    import jax.numpy as jnp

    return {"x": jnp.asarray(batch["x"]),
            "blocks": [{k: jnp.asarray(v) for k, v in b.items()}
                       for b in batch["blocks"]],
            "labels": jnp.asarray(batch["labels"]),
            "mask": jnp.asarray(batch["mask"])}


def loss_fn(forward, params, batch, control: bool = False):
    """Mean softmax cross entropy of ``forward``'s logits over the batch's
    seeds."""
    import jax
    import jax.numpy as jnp

    logits = forward(params, batch["x"], batch["blocks"], control)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    mask = batch["mask"]
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


def adamw_step(params, state, grads, opt: dict):
    """One AdamW update; ``state`` is ``(t, m, v)``."""
    import jax
    import jax.numpy as jnp

    t, m, v = state
    t = t + 1
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, s: p - opt["lr"] * ((a / c1) / (jnp.sqrt(s / c2)
                                                      + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v,
    )
    return params, (t, m, v)


def train(forward, params0, batches: list[dict], opt: dict,
          control: bool = False) -> dict:
    """Follow the program through ``len(batches)`` steps from ``params0``,
    with the model's ``forward``.

    Returns the loss of each step, the first step's gradient and the
    parameters after the last step, all on the host.
    """
    import jax

    def to_host(tree):
        return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)

    grad = jax.jit(jax.value_and_grad(loss_fn, argnums=1),
                   static_argnums=(0, 3))
    params = params0
    state = (0, jax.tree.map(lambda p: p * 0, params0),
             jax.tree.map(lambda p: p * 0, params0))
    losses, first_grad = [], None
    for batch in batches:
        loss, g = grad(forward, params, on_device(batch), control)
        if first_grad is None:
            first_grad = to_host(g)
        params, state = adamw_step(params, state, g, opt)
        losses.append(float(loss))
    return {"losses": losses, "grad": first_grad, "params": to_host(params)}


def _leaves(tree) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def _norm_gap(got: dict, want: dict, counted=None) -> tuple[float, str]:
    """Worst leaf ``|‖got‖ − ‖want‖|`` over the larger of ``‖want‖`` and
    the median leaf's ``‖want‖``, and that leaf's name."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    worst, leaf = 0.0, ""
    for k, n in norms.items():
        if counted is not None and k not in counted:
            continue
        gap = abs(float(np.linalg.norm(got[k])) - n) / max(n, med, 1e-30)
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def compare(obs: dict, ref: dict, params0, b1: float) -> dict:
    """The numbers compared, from the program's observations ``obs``:

    - ``x_rows``: largest |difference| between the feature rows the step
      consumed and the plain table's rows;
    - ``loss``: largest relative gap of a step's loss;
    - ``grad_norm``: worst leaf gap of the first gradient's norm, read from
      the optimizer's first moment after one step (``mu / (1 - b1)``);
    - ``update_norm``: worst leaf gap of the norm of the parameters' change
      over the compared steps, over the leaves the reference moves
      (``update_leaf`` names the worst).
    """
    x_gap = max(float(np.max(np.abs(x - want))) if x.size else 0.0
                for x, want in zip(obs["x"], obs["x_want"]))
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(obs["losses"], ref["losses"]))
    g_ref = _leaves(ref["grad"])
    g_got = {k: v / (1 - b1) for k, v in _leaves(obs["mu"]).items()}
    gnorms = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    gmed = float(np.median(list(gnorms.values())))
    counted = {k for k, n in gnorms.items() if n >= NEGLIGIBLE_GRAD * gmed}
    p0 = _leaves(params0)
    d_ref = {k: v - p0[k] for k, v in _leaves(ref["params"]).items()}
    d_got = {k: v - p0[k] for k, v in _leaves(obs["params"]).items()}
    update_gap, update_leaf = _norm_gap(d_got, d_ref, counted)
    return {
        "x_rows": x_gap,
        "loss": loss_gap,
        "grad_norm": _norm_gap(g_got, g_ref)[0],
        "update_norm": update_gap,
        "update_leaf": update_leaf,
    }
