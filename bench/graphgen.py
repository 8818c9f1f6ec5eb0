"""The benchmark's graph: the Graph500 Kronecker generator, coded from
the Graph500 specification (section 3, "Graph Generation").

The generator draws ``edgefactor * 2**scale`` edges.  Each edge picks
one quadrant of the adjacency matrix per bit of the vertex number, with
the initiator probabilities A, B, C and D = 1 - A - B - C (0.57, 0.19,
0.19, 0.05 in the specification); the vertex numbers are then permuted
at random.  The graph is undirected: the CSR holds both directions of
every edge, with self loops dropped and parallel edges merged, rows
sorted.  Vertices that no edge touches stay in the graph with degree 0,
as in the specification; walks start only from vertices of degree 1 or
more (Graph500 draws its search keys the same way).

The topology comes from the configuration's own ``graph.seed`` and is
cached on disk; the edge weights are drawn anew from every run's
``--seed``, from the configuration's weight law.  So every seed walks
the same graph, and no two seeds walk the same weights.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache" / "graphs"
PARAMS = ("scale", "edgefactor", "a", "b", "c", "seed")


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float,
                    c: float, seed: int):
    """(src, dst) int64 arrays of the specification's edge list, as its
    reference ``kronecker_generator`` draws it (0-based)."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[ii], perm[jj]


def kronecker_topology(scale: int, edgefactor: int, a: float, b: float,
                       c: float, seed: int):
    """(indptr int32 [V+1], indices int32 [E]) of the undirected graph:
    both directions of every edge, no self loops, no parallel edges."""
    n = 1 << scale
    ii, jj = kronecker_edges(scale, edgefactor, a, b, c, seed)
    keep = ii != jj
    ii, jj = ii[keep], jj[keep]
    # np.unique sorts the (src, dst) keys: rows come out contiguous and
    # sorted within each row, as the CSR layout needs
    key = np.unique(np.concatenate([ii * n + jj, jj * n + ii]))
    del ii, jj
    indices = (key % n).astype(np.int32)
    counts = np.bincount(key // n, minlength=n)
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    return indptr, indices


def topology(graph_cfg: dict, cache: bool = True):
    """The configuration's topology, from the on-disk cache when it is
    there (keyed by every generator parameter)."""
    if graph_cfg["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown graph generator {graph_cfg['generator']!r}")
    params = {k: graph_cfg[k] for k in PARAMS}
    tag = hashlib.sha256(json.dumps(params, sort_keys=True).encode()
                         ).hexdigest()[:16]
    path = CACHE / f"kronecker-{tag}.npz"
    if cache and path.exists():
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    indptr, indices = kronecker_topology(**params)
    if cache:
        CACHE.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
        np.savez(tmp, indptr=indptr, indices=indices)
        os.replace(tmp, path)
    return indptr, indices


def edge_weights(graph_cfg: dict, num_edges: int, seed: int) -> np.ndarray:
    """The run's edge weights, float32, from ``--seed``."""
    rng = np.random.default_rng([int(seed), 0x6ea7])
    law = graph_cfg["weights"]
    if law == "uniform":  # the paper's default: U[1, 5)
        return rng.uniform(1.0, 5.0, size=num_edges).astype(np.float32)
    raise ValueError(f"unknown weight law {law!r}")


def make_graph(graph_cfg: dict, seed: int, cache: bool = True):
    """Host arrays (indptr, indices, h) of the run's graph."""
    indptr, indices = topology(graph_cfg, cache)
    return indptr, indices, edge_weights(graph_cfg, indices.shape[0], seed)


def walk_starts(indptr: np.ndarray) -> np.ndarray:
    """The vertices a walk may start from: those of degree 1 or more."""
    return np.nonzero(np.diff(indptr) > 0)[0].astype(np.int32)
