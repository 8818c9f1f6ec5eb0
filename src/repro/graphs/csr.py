"""CSR graph representation (a JAX pytree) and per-node preprocessing.

Design notes
------------
* ``indices`` is sorted within each row — this makes ``dist(v', u)`` (the
  Node2Vec/2nd-PR "is u a neighbour of the previous node" test) a binary
  search (:func:`has_edge`), vectorisable with ``vmap``.  Its depth is
  static: :func:`search_depth` of a bound on every row's length (the
  engine's padded row length ``pad``), since each round is one gather of
  every candidate, converged or not.
* ``node_stats`` is the JAX equivalent of the code Flexi-Compiler *generates*
  for ``preprocess()`` (paper Fig. 9d): per-node h_MAX / h_MIN / h_SUM /
  h_MEAN pointers, computed with segment reductions.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Directed graph in CSR form.  All fields are device arrays.

    indptr:  [V+1] int32 — row offsets.
    indices: [E] int32   — destination of each edge, sorted within a row.
    h:       [E] float32 — edge *property* weights (the dataset's weights).
    labels:  [E] int32   — edge labels (MetaPath); zeros when unlabeled.
    """

    indptr: jax.Array
    indices: jax.Array
    h: jax.Array
    labels: jax.Array

    @property
    def num_nodes(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[0]

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def max_degree(self) -> int:
        return int(jnp.max(self.degrees()))

    # Row-accessor protocol shared with graphs.delta.OverlayGraph: every
    # sampling path reads rows through these two (never indptr directly),
    # so a delta-overlay graph — whose rows are NOT contiguous — runs the
    # same kernels unchanged.
    def row_starts(self, v: jax.Array) -> jax.Array:
        """Edge-array offset of each node's row (``v`` may be batched)."""
        return self.indptr[v]

    def row_degs(self, v: jax.Array) -> jax.Array:
        """Degree of each node's row (``v`` may be batched)."""
        return self.indptr[v + 1] - self.indptr[v]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NodeStats:
    """Per-node statistics of the edge property weight h.

    This is the materialisation of Flexi-Compiler's generated
    ``preprocess()``: the h_MAX / h_SUM (and friends) pointers of Fig. 9d.
    """

    h_min: jax.Array  # [V] float32
    h_max: jax.Array  # [V] float32
    h_sum: jax.Array  # [V] float32
    h_mean: jax.Array  # [V] float32
    degree: jax.Array  # [V] int32
    label_count: jax.Array  # [V, L] int32 — #edges per label per node (MetaPath)


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    num_nodes: int,
    h: Optional[np.ndarray] = None,
    labels: Optional[np.ndarray] = None,
) -> CSRGraph:
    """Build a CSRGraph from an edge list (host-side, numpy)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if h is None:
        h = np.ones(src.shape[0], dtype=np.float32)
    if labels is None:
        labels = np.zeros(src.shape[0], dtype=np.int32)
    # Sort by (src, dst) so rows are contiguous and sorted.
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    h, labels = np.asarray(h, np.float32)[order], np.asarray(labels, np.int32)[order]
    counts = np.bincount(src, minlength=num_nodes)
    indptr = np.zeros(num_nodes + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    return CSRGraph(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(dst, jnp.int32),
        h=jnp.asarray(h, jnp.float32),
        labels=jnp.asarray(labels, jnp.int32),
    )


def node_stats(graph: CSRGraph, num_labels: int = 8) -> NodeStats:
    """Segment min/max/sum/mean of h per node + per-label edge counts.

    Pure JAX (jit-able); this is the one-time preprocessing whose cost the
    paper reports in Table 3 ("Preproc.").
    """
    V = graph.num_nodes
    E = graph.num_edges
    deg = graph.degrees()
    # segment id of each edge = its source row.
    seg = jnp.repeat(jnp.arange(V, dtype=jnp.int32), deg, total_repeat_length=E)
    h_min = jax.ops.segment_min(graph.h, seg, num_segments=V)
    h_max = jax.ops.segment_max(graph.h, seg, num_segments=V)
    h_sum = jax.ops.segment_sum(graph.h, seg, num_segments=V)
    # Degenerate rows (deg == 0): segment_min/max give +inf/-inf; clamp to 0.
    safe_deg = jnp.maximum(deg, 1)
    h_mean = h_sum / safe_deg.astype(jnp.float32)
    h_min = jnp.where(deg > 0, h_min, 0.0)
    h_max = jnp.where(deg > 0, h_max, 0.0)
    lbl_seg = seg * num_labels + jnp.clip(graph.labels, 0, num_labels - 1)
    label_count = jax.ops.segment_sum(
        jnp.ones((E,), jnp.int32), lbl_seg, num_segments=V * num_labels
    ).reshape(V, num_labels)
    return NodeStats(
        h_min=h_min,
        h_max=h_max,
        h_sum=h_sum,
        h_mean=h_mean,
        degree=deg,
        label_count=label_count,
    )


def neighbor_slice(graph: CSRGraph, v: jax.Array, width: int):
    """Gather a fixed-width window of v's adjacency (padded).

    Returns (nbr_idx, nbr_h, nbr_labels, mask) each of shape [width].
    Out-of-row lanes are masked (idx = -1, h = 0).
    """
    start = graph.row_starts(v)
    deg = graph.row_degs(v)
    offs = jnp.arange(width, dtype=jnp.int32)
    mask = offs < deg
    pos = jnp.clip(start + offs, 0, graph.num_edges - 1)
    nbr = jnp.where(mask, graph.indices[pos], -1)
    hh = jnp.where(mask, graph.h[pos], 0.0)
    ll = jnp.where(mask, graph.labels[pos], -1)
    return nbr, hh, ll, mask


def search_depth(max_degree: int) -> int:
    """Binary-search iterations guaranteed to converge for rows with at
    most ``max_degree`` neighbours (+1 slack).  Must be computed from a
    *static* bound (e.g. ``SamplerContext.pad``) — inside a jitted epoch
    the graph arrays are tracers, so the depth cannot be derived there.
    Extra iterations past convergence are no-ops (the ``lo < hi`` guard),
    which is why any sufficient depth gives the same answer bit for bit
    (and matches the Pallas kernels' run-to-convergence ``while_loop``)."""
    return int(np.ceil(np.log2(max(max_degree, 1) + 1))) + 1


#: :func:`search_depth` of the longest int32 row: the depth that needs no
#: bound on the rows
INT32_DEPTH = 32


@partial(jax.jit, static_argnames=("depth",))
def has_edge(graph: CSRGraph, v: jax.Array, u: jax.Array,
             depth: int = INT32_DEPTH) -> jax.Array:
    """True iff edge (v, u) exists.  Fixed-depth binary search on the sorted
    row ``indices[indptr[v]:indptr[v+1]]`` — vectorise with vmap over (v, u).

    ``depth`` must cover v's row: :func:`search_depth` of any bound on the
    row lengths (the default covers every int32 row).  Each iteration is
    one gather whether or not the search has converged, so the depth is
    the search's cost.  Handles v == -1 (no previous node yet) by
    returning False.
    """
    valid = v >= 0
    vs = jnp.maximum(v, 0)
    lo = graph.row_starts(vs)
    hi = lo + graph.row_degs(vs)

    def body(_, carry):
        lo, hi, found = carry
        mid = (lo + hi) // 2
        mid_val = graph.indices[jnp.clip(mid, 0, graph.num_edges - 1)]
        live = lo < hi
        # a search that finds u probes it: the first entry >= u is where
        # hi last moved, and hi only moves to a probed mid
        found = found | (live & (mid_val == u))
        go_right = jnp.logical_and(mid_val < u, live)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(jnp.logical_or(go_right, ~live), hi, mid)
        return (new_lo, new_hi, found)

    _, _, found = jax.lax.fori_loop(
        0, depth, body, (lo, hi, jnp.zeros(jnp.shape(lo), bool)))
    return jnp.logical_and(valid, found)


def dist_code(graph: CSRGraph, v_prev: jax.Array, u: jax.Array,
              depth: int = INT32_DEPTH) -> jax.Array:
    """Node2Vec's dist(v', u) ∈ {0, 1, 2}: 0 if u == v', 1 if (v'→u) ∈ E,
    else 2.  v' == -1 (first step) returns 1 ("stay neutral"), matching the
    usual first-step semantics of Node2Vec implementations.  ``depth`` is
    :func:`has_edge`'s.
    """
    is_prev = u == v_prev
    connected = has_edge(graph, v_prev, u, depth)
    d = jnp.where(is_prev, 0, jnp.where(connected, 1, 2))
    return jnp.where(v_prev < 0, 1, d).astype(jnp.int32)
