"""The benchmark's data: graph, partition and policy, cached per checkout.

The graph generator below is the benchmark's own copy of the program's
``graph/synthetic.power_law_graph`` (community-structured configuration
model with zipf hubs), so that a change to the program cannot change the
data it is judged on. It returns plain arrays, not the program's ``Graph``.

Fixtures are made with a fixed seed (0), as ``gnn_trainer.build_trace``
makes them, and cached under ``.cache/<config>-<hash>/`` inside the
benchmark directory, keyed by the configuration file's content: only the
first run of a cell in a checkout pays for them.

- graph: the CSR over incoming edges, features and labels;
- partition: the program's ``partition_graph`` over the generated edges;
- policy: the program's ``get_or_train_policy``, so that a change to DQN
  training still shows its effect.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_ROOT = os.path.join(BENCH_DIR, ".cache")
FIXTURE_SEED = 0


def power_law_graph(n_nodes: int, n_edges: int, n_feat: int, n_classes: int,
                    n_communities: int, zipf_a: float, intra_frac: float,
                    seed: int) -> dict:
    """Edges, features and labels of a community-structured power-law graph.

    Edges attach preferentially to low-rank (hub) nodes; ``intra_frac`` of
    edges stay within the destination's community. Labels are the community
    modulo ``n_classes``; features are unit normal plus half a class centre.
    Returns ``src``, ``dst`` (int64, self loops removed), ``features``
    (float32) and ``labels`` (int32).
    """
    rng = np.random.default_rng(seed)
    community = rng.integers(0, n_communities, n_nodes)
    rank_of = rng.permutation(n_nodes)

    dst = rng.integers(0, n_nodes, n_edges)
    ranks = (rng.zipf(zipf_a, n_edges) - 1).clip(0, n_nodes - 1)
    src = rank_of[ranks]
    intra = rng.random(n_edges) < intra_frac
    comm_sorted = np.argsort(community, kind="stable")
    comm_counts = np.bincount(community, minlength=n_communities)
    comm_start = np.zeros(n_communities + 1, np.int64)
    np.cumsum(comm_counts, out=comm_start[1:])
    c = community[dst[intra]]
    offsets = (rng.random(intra.sum()) * comm_counts[c]).astype(np.int64)
    src[intra] = comm_sorted[
        comm_start[c] + np.minimum(offsets, comm_counts[c] - 1)
    ]
    keep = src != dst
    features = rng.standard_normal((n_nodes, n_feat)).astype(np.float32)
    labels = (community % n_classes).astype(np.int32)
    centers = rng.standard_normal((n_classes, n_feat)).astype(np.float32)
    features += 0.5 * centers[labels]
    return {"src": src[keep].astype(np.int64), "dst": dst[keep].astype(np.int64),
            "features": features, "labels": labels}


def to_csr(src: np.ndarray, dst: np.ndarray, n_nodes: int):
    """In-neighbour CSR: ``indices[indptr[v]:indptr[v+1]]`` are the sources
    of edges into ``v``, in edge order (a stable sort by destination)."""
    order = np.argsort(dst, kind="stable")
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n_nodes), out=indptr[1:])
    return indptr, src[order].astype(np.int32)


def cache_dir(config: dict) -> str:
    """``.cache/<name>-<hash>``: the hash is of the configuration's content
    (its limits aside, which no fixture depends on)."""
    content = {k: v for k, v in config.items() if k != "limits"}
    blob = json.dumps(content, sort_keys=True).encode()
    key = hashlib.sha256(blob).hexdigest()[:12]
    return os.path.join(CACHE_ROOT, f"{config['name']}-{key}")


def _save(path: str, **arrays) -> None:
    tmp = path + ".tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)


def graph_arrays(config: dict, log=print) -> dict:
    """The cached graph and partition of ``config``, made on first use.

    Returns ``indptr``, ``indices``, ``features``, ``labels`` and ``owner``.
    """
    import time

    d = cache_dir(config)
    path = os.path.join(d, "graph.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    os.makedirs(d, exist_ok=True)
    g = config["graph"]
    t0 = time.perf_counter()
    arr = power_law_graph(
        n_nodes=g["n_nodes"], n_edges=g["n_edges"], n_feat=g["n_feat"],
        n_classes=g["n_classes"], n_communities=g["n_communities"],
        zipf_a=g["zipf_a"], intra_frac=g["intra_frac"], seed=FIXTURE_SEED,
    )
    log(f"fixture: graph generated in {time.perf_counter() - t0:.1f} s "
        f"({len(arr['src'])} edges)")
    t0 = time.perf_counter()
    owner = _partition(config, arr)
    log(f"fixture: partition in {time.perf_counter() - t0:.1f} s")
    indptr, indices = to_csr(arr["src"], arr["dst"], g["n_nodes"])
    out = {"indptr": indptr, "indices": indices, "features": arr["features"],
           "labels": arr["labels"], "owner": owner}
    _save(path, **out)
    return out


def _partition(config: dict, arr: dict) -> np.ndarray:
    from repro.graph.partition import partition_graph
    from repro.graph.structure import Graph

    graph = Graph(n_nodes=config["graph"]["n_nodes"],
                  edge_index=np.stack([arr["src"], arr["dst"]]),
                  labels=arr["labels"])
    return np.asarray(
        partition_graph(graph, config["training"]["n_parts"],
                        seed=FIXTURE_SEED),
        np.int32,
    )


def program_graph(arrays: dict):
    """The program's ``Graph`` over the cached arrays, its CSR prebuilt."""
    from repro.graph.structure import CSR, Graph

    indptr, indices = arrays["indptr"], arrays["indices"]
    n = len(indptr) - 1
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    return Graph(
        n_nodes=n,
        edge_index=np.stack([indices.astype(np.int64), dst]),
        features=arrays["features"], labels=arrays["labels"],
        _csr=CSR(indptr=indptr, indices=indices.astype(np.int64)),
    )


def policy(config: dict, params, log=print):
    """The lane's DQN policy, trained once per checkout by the program.

    ``REPRO_ARTIFACTS`` has to point at ``cache_dir(config)`` before the
    program's policy module is first imported (the caller sets it).
    """
    import time

    from repro.train import policy as pol

    lane = config["lane"]
    t0 = time.perf_counter()
    q_fn, _ = pol.get_or_train_policy(
        pol.make_params_pool([params]), name="qnet",
        iterations=lane["policy_iterations"],
    )
    log(f"fixture: policy ready in {time.perf_counter() - t0:.1f} s")
    return q_fn
