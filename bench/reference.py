"""Plain numpy reference of the walk programs, and the comparison that
decides a run's ``correct``.

Nothing here imports the program under test.  The reference reads the
benchmark's own host copy of the graph (``graphgen``) and the program
parameters from the configuration file, and states each program's
semantics directly:

* ``deepwalk`` (weighted): from v, move to neighbour x with probability
  h(v, x) / sum of h over v's row;
* ``node2vec`` (weighted, return parameter a, in-out parameter b): the
  weight of x is h(v, x) times 1/a if x is the previous node, 1 if x is
  an out-neighbour of the previous node, 1/b otherwise; on the first
  hop (no previous node) the factor is 1;
* ``ppr_nibble``: deepwalk's moves, and a residual mass that starts at
  1 and is multiplied by (1 - alpha) on every hop; the walk stops right
  after the hop out of v for which mass < eps * deg(v), else after
  ``walk_len`` hops.  Computed in float32, the precision the
  configuration states.

What is compared (see ``compare``):

* ``bad_hops`` — path entries that break the walk's form: a path that
  does not begin at its start node, a hop that is not an edge of the
  graph, an entry after the walk's end.  Exact: limit 0.
* ``bad_lengths`` — walks whose number of hops disagrees with the
  program: fixed-length walks shorter than ``walk_len`` once complete,
  ppr_nibble walks that stop where the rule says go on or go on where it
  says stop, in-flight walks whose hop count disagrees with the epochs
  they were served.  Exact: limit 0.
* ``pit_ks`` — the hop distribution.  For a seeded sample of hops
  (prev, v, x), the randomized probability integral transform
  u = F(x-) + V * p(x), with F the reference's cumulative transition
  probability over v's row in CSR order and V ~ U(0, 1), is uniform on
  (0, 1) and independent from hop to hop exactly when the sampler draws
  from the reference's distribution.  The number compared is
  sqrt(n) * KS distance of the u's from uniform, whose law under a
  correct sampler is Kolmogorov's whatever the graph.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


class Graph:
    """Host CSR arrays of the run's graph (indptr, indices, h)."""

    def __init__(self, indptr, indices, h):
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices, np.int64)
        self.h = np.asarray(h, np.float32)
        self.V = self.indptr.shape[0] - 1
        self.deg = np.diff(self.indptr)
        self._keys = None

    def row(self, v: int):
        lo, hi = self.indptr[v], self.indptr[v + 1]
        return self.indices[lo:hi], self.h[lo:hi]

    def is_edge(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Vectorised edge test (rows are sorted, so the keys are)."""
        if self._keys is None:
            self._keys = (np.repeat(np.arange(self.V, dtype=np.int64),
                                    self.deg) * self.V + self.indices)
        q = src.astype(np.int64) * self.V + dst.astype(np.int64)
        pos = np.minimum(np.searchsorted(self._keys, q),
                         self._keys.shape[0] - 1)
        return (self._keys[pos] == q) & (src >= 0) & (dst >= 0) \
            & (src < self.V) & (dst < self.V)


# ----------------------------------------------------------- programs
def row_weights(spec: dict, g: Graph, prev: int, v: int,
                dtype=np.float64):
    """(neighbours, transition weights) of v's row, in CSR order, in
    ``dtype`` arithmetic (float64 for the reference, bfloat16 for the
    control)."""
    nbr, h = g.row(v)
    w = h.astype(dtype)
    if spec["kind"] == "node2vec" and prev >= 0:
        prow, _ = g.row(prev)
        pos = np.minimum(np.searchsorted(prow, nbr), max(prow.size - 1, 0))
        member = (prow[pos] == nbr) if prow.size else np.zeros(nbr.size,
                                                                bool)
        bias = np.where(nbr == prev, 1.0 / spec["a"],
                        np.where(member, 1.0, 1.0 / spec["b"]))
        w = w * bias.astype(dtype)
    elif spec["kind"] not in ("deepwalk", "ppr_nibble", "node2vec"):
        raise ValueError(f"no reference for program kind {spec['kind']!r}")
    return nbr, w


def pit_values(spec: dict, g: Graph, prev, cur, nxt,
               rng: np.random.Generator) -> np.ndarray:
    """Randomized PIT of each hop cur -> nxt (previous node ``prev``)
    under the reference; nan where nxt is not in cur's row."""
    n = len(cur)
    jitter = rng.random(n)
    out = np.full(n, np.nan)
    for i in range(n):
        nbr, w = row_weights(spec, g, int(prev[i]), int(cur[i]))
        j = int(np.searchsorted(nbr, nxt[i]))
        if j >= nbr.size or nbr[j] != nxt[i]:
            continue
        out[i] = (w[:j].sum() + jitter[i] * w[j]) / w.sum()
    return out


def ks_sqrt_n(u: np.ndarray) -> float:
    """sqrt(n) times the Kolmogorov–Smirnov distance of ``u`` from
    U(0, 1); +inf when any value is missing (a hop off the graph)."""
    u = np.sort(np.asarray(u, np.float64))
    n = u.size
    if n == 0:
        return 0.0
    if not np.isfinite(u).all():
        return float("inf")
    i = np.arange(1, n + 1)
    d = max(float((i / n - u).max()), float((u - (i - 1) / n).max()))
    return float(np.sqrt(n) * d)


def ppr_stops(spec: dict, degs: np.ndarray, dtype=np.float32) -> np.ndarray:
    """Stop verdict after each hop of a walk whose hops leave nodes of
    degree ``degs`` (in order): True where mass < eps * deg."""
    one, decay = dtype(1.0), dtype(1.0 - spec["alpha"])
    eps = dtype(spec["eps"])
    mass = np.empty(degs.size, dtype)
    m = one
    for k in range(degs.size):
        m = dtype(m * decay)
        mass[k] = m
    return mass < (eps * degs.astype(dtype)).astype(dtype)


# ------------------------------------------------------------ control
def control_draws(spec: dict, g: Graph, prev, cur,
                  rng: np.random.Generator) -> np.ndarray:
    """The reference computed in bfloat16 (weights, factors and the
    row's running sum) put in the sampler's place: one draw of the next
    node at each position (prev, cur)."""
    n = len(cur)
    r = rng.random(n)
    out = np.empty(n, np.int64)
    for i in range(n):
        nbr, w = row_weights(spec, g, int(prev[i]), int(cur[i]), BF16)
        cum = np.cumsum(w, dtype=BF16)
        j = int(np.searchsorted(cum, BF16(r[i]) * cum[-1], side="right"))
        out[i] = nbr[min(j, nbr.size - 1)]
    return out


# ---------------------------------------------------------- comparison
def walk_hops(paths: np.ndarray):
    """(query row, hop index k>=1, prev, cur, nxt) of every emitted hop
    of ``paths`` [Q, L+1] (-1 past a walk's end)."""
    live = paths[:, 1:] >= 0
    q, k = np.nonzero(live)
    cur = paths[q, k]
    nxt = paths[q, k + 1]
    prev = np.where(k > 0, paths[q, np.maximum(k - 1, 0)], -1)
    return q, k + 1, prev, cur, nxt


def form_errors(g: Graph, starts: np.ndarray, paths: np.ndarray) -> int:
    """Count of path entries that break a walk's form (``bad_hops``)."""
    bad = int((paths[:, 0] != starts).sum())
    # an entry after the end (a -1 followed by a node)
    bad += int(((paths[:, :-1] < 0) & (paths[:, 1:] >= 0)).sum())
    _, _, _, cur, nxt = walk_hops(paths)
    ok = cur >= 0
    bad += int((~g.is_edge(cur[ok], nxt[ok])).sum()) + int((~ok).sum())
    return bad


def length_errors(spec: dict, g: Graph, paths: np.ndarray,
                  complete: np.ndarray, expect_hops=None) -> int:
    """Count of walks whose hop count disagrees with the program
    (``bad_lengths``).  ``complete`` marks walks the system reported
    finished; ``expect_hops`` (optional) gives the hop count an
    unfinished walk must have by now."""
    L = paths.shape[1] - 1
    hops = (paths[:, 1:] >= 0).sum(axis=1)
    bad = 0
    for i in np.nonzero(complete)[0]:
        n = int(hops[i])
        if spec["kind"] != "ppr_nibble":
            bad += n != L
            continue
        stops = ppr_stops(spec, g.deg[paths[i, :n]])
        # the first hop whose verdict is stop ends the walk
        bad += (int(np.argmax(stops)) + 1 if stops.any() else L) != n
    if expect_hops is not None:
        for i in np.nonzero(~complete)[0]:
            n = int(hops[i])
            early = spec["kind"] == "ppr_nibble" and \
                bool(ppr_stops(spec, g.deg[paths[i, :n]]).any())
            bad += early or n != min(int(expect_hops[i]), L)
    return bad


def control_stop_disagreements(spec: dict, g: Graph,
                               paths: np.ndarray) -> int:
    """The control's stop rule (mass and threshold in bfloat16) at every
    hop of ``paths``: the number of walks on which any of its verdicts
    differs from the reference's."""
    if spec["kind"] != "ppr_nibble":
        return 0
    bad = 0
    for path in paths:
        degs = g.deg[path[:-1][path[1:] >= 0]]
        bad += bool((ppr_stops(spec, degs, BF16)
                     != ppr_stops(spec, degs)).any())
    return bad


def sample_hops(paths: np.ndarray, n: int, rng: np.random.Generator):
    """A seeded sample of at most ``n`` emitted hops (prev, cur, nxt)."""
    _, _, prev, cur, nxt = walk_hops(paths)
    if cur.size > n:
        pick = np.sort(rng.choice(cur.size, n, replace=False))
        prev, cur, nxt = prev[pick], cur[pick], nxt[pick]
    return prev, cur, nxt
