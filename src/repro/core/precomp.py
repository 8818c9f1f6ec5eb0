"""Precomputed-regime tables (C-SAW-style static sampling; paper §2.2/§6).

For workloads whose ``get_weight`` the Flexi-Compiler proves state-
independent (:func:`repro.core.flexi_compiler.is_static` — output taint
disjoint from ``dist``/``prev``/``deg_prev``/``step``), the transition
distribution of every node is a constant of the graph.  This module bakes
it once into two table families:

* **ITS** — per-row inclusive prefix sums of w̃ (``cdf``) + row totals.
  A draw is ``u·total`` followed by a *binary search* of the row: O(log d)
  per step, no weight evaluation, no RNG retries.
* **Alias** — Vose tables (``alias_off``/``alias_prob``), built host-side
  in float64.  A draw is two uniforms and two gathers: O(1) per step.

Both are one-time preprocessing (the Table-3 "Preproc." budget); C-SAW
shows this regime dominates static-weight workloads, which is why the
extended cost model (``CostModel.prefer_precomp``) routes static-provable
nodes here ahead of the Eq. 11 rejection/reservoir split.

Tables carry **two layouts of the same values**: the flat CSR-order
arrays the jnp selectors read, and the tile-aligned [R, 128] streams
(``ops.align_rows`` geometry) the Pallas kernels in
``kernels/precomp_kernel.py`` DMA.  The jnp selectors and the kernels
consume the *same* counter-based Threefry uniforms
(:func:`threefry_seeds` + the per-kernel salts), so the two execution
paths — selected by ``EngineConfig.precomp_exec`` — are bit-identical.

**Invalidation and amortized rebuild**: mutating a node's edge weights
makes its row stale.  ``PrecompTables.invalid`` is a per-node bitmap —
samplers route lanes whose current node is invalidated to the dynamic
path (eRVS over the *live* graph), so mutation costs one bitmap write
up front.  Stale rows then enter a :class:`RebuildQueue` which the
engine drains a budgeted few rows per scheduler epoch
(``EngineConfig.rebuild_budget``): each drained row is re-baked from the
current graph with the *same float64 math* as a fresh build
(:func:`rebuild_rows` is bit-identical to :func:`build_tables` row by
row: both run the bucketed builder :func:`_table_rows`, which equals the
per-row definition :func:`_row_tables`), and its validity bit flips
back — the fallback is transient, never permanent.
``WalkEngine.update_graph`` is the engine-level entry point.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ctxutil import degrees_of
from repro.core.types import EdgeCtx, Workload
from repro.graphs.csr import INT32_DEPTH, CSRGraph
from repro.graphs.delta import host_row_layout
# Threefry counter salts and the key derivation live in kernels/prng.py,
# shared with kernels/precomp_kernel.py, the mega-step kernel and the
# kernels/ref.py oracles, so table draws never collide with the uniforms
# any other sampler derives from the same per-(walker, step) stream key.
from repro.kernels.prng import (ALIAS_SALT, ITS_SALT, threefry_seeds,
                                uniform_01, uniform_pair_01)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PrecompTables:
    """Per-node ITS + alias tables over the CSR edge order, plus the
    invalidation bitmap.  A registered pytree: the engine passes it into
    the jitted epoch as a runtime argument, so background row rebuilds
    swap in new arrays with **no retrace** (shapes never change)."""

    cdf: jax.Array  # [E] f32 — row-local inclusive prefix sums of w̃
    total: jax.Array  # [V] f32 — row sums (cdf value at each row's end)
    alias_off: jax.Array  # [E] i32 — alias partner offset within the row
    alias_prob: jax.Array  # [E] f32 — acceptance probability of the column
    invalid: jax.Array  # [V] bool — rows that must use the dynamic path
    # tile-aligned [R, 128] streams of the same values (ops.align_rows
    # geometry) + the first aligned 128-row per node — the layout the
    # Pallas kernels DMA.  None for hand-built tables; the kernel path
    # then degrades to the (bit-identical) jnp selectors.
    cdf2d: Optional[jax.Array] = None
    prob2d: Optional[jax.Array] = None
    alias2d: Optional[jax.Array] = None
    arow0: Optional[jax.Array] = None  # [V] i32

    def invalidate(self, nodes) -> "PrecompTables":
        """Mark ``nodes``' rows stale (their lanes fall back to the dynamic
        path).  Returns a new object; tables are immutable."""
        idx = jnp.asarray(np.asarray(nodes), jnp.int32)
        return dataclasses.replace(
            self, invalid=self.invalid.at[idx].set(True))

    def row_valid(self, v: jax.Array) -> jax.Array:
        """Per-lane: may this node be served from the tables?"""
        vs = jnp.maximum(v, 0)
        return (v >= 0) & ~self.invalid[vs]

    def frac_stale(self) -> jax.Array:
        """Scalar f32: fraction of table rows currently invalidated (the
        transient-fallback fraction ``CostModel.prefer_precomp`` discounts
        routing by while the rebuild queue drains)."""
        return jnp.mean(self.invalid.astype(jnp.float32))

    def with_aligned(self, indptr) -> "PrecompTables":
        """Attach the tile-aligned kernel layout (rebuilt from the flat
        arrays; geometry is a function of the topology only)."""
        # deferred import: ops pulls the Pallas kernel modules, which
        # flat-only (aligned=False) builds never need
        from repro.kernels import ops as kernel_ops

        cdf2d, prob2d, alias2d, row0, _ = kernel_ops.aligned_precomp_tables(
            self, np.asarray(indptr))
        return dataclasses.replace(self, cdf2d=cdf2d, prob2d=prob2d,
                                   alias2d=alias2d, arow0=row0)


def edge_weights_static(graph: CSRGraph, workload: Workload,
                        params) -> jax.Array:
    """w̃ for every edge of a *static* workload, in CSR order ([E] f32).

    Because ``is_static`` proved the output ignores dist/prev/deg_prev/step,
    those fields are filled with neutral placeholders (dist=1, prev=-1,
    step=0) — any values would give the same weights.
    """
    V, E = graph.num_nodes, graph.num_edges
    deg = graph.degrees()
    src = jnp.repeat(jnp.arange(V, dtype=jnp.int32), deg,
                     total_repeat_length=E)
    return _eval_static_weights(graph, workload, params,
                                jnp.arange(E, dtype=jnp.int32), src,
                                deg[src])


def _eval_static_weights(graph: CSRGraph, workload: Workload, params,
                         edge_idx: jax.Array, src: jax.Array,
                         deg_cur: jax.Array) -> jax.Array:
    """Static w̃ of the listed edges ([n] f32), with the same neutral
    placeholder context as :func:`edge_weights_static` — the shared
    evaluator that keeps full builds and row rebuilds bit-identical."""
    n = edge_idx.shape[0]
    ctx = EdgeCtx(
        h=(graph.h[edge_idx] if workload.weighted
           else jnp.ones((n,), jnp.float32)),
        label=graph.labels[edge_idx],
        dist=jnp.ones((n,), jnp.int32),
        nbr=graph.indices[edge_idx],
        deg_cur=deg_cur,
        deg_prev=jnp.zeros((n,), jnp.int32),
        cur=src,
        prev=jnp.full((n,), -1, jnp.int32),
        step=jnp.zeros((n,), jnp.int32),
    )
    # ``is_static`` also proved the weights ignore the program's per-walker
    # state, so any representative value works — use the initial state.
    ws0 = workload.wstate_template()
    w = jax.vmap(lambda c: workload.edge_weight(c, params, ws0))(ctx)
    return jnp.maximum(w, 0.0).astype(jnp.float32)


def _vose_row(ww: np.ndarray, tot: float) -> Tuple[np.ndarray, np.ndarray]:
    """Textbook two-stack Vose alias construction for ONE row, float64,
    normalised by ``tot`` (the row's float64 prefix-sum total, the value
    its ITS ``total`` rounds from).  The per-row definition the bucketed
    :func:`_table_rows` reproduces bit for bit.  Zero-total rows keep the
    neutral (alias=self-ish, prob=1) fill — ``total[v] == 0`` masks them
    at draw time."""
    d = ww.shape[0]
    alias = np.zeros(d, np.int32)
    prob = np.ones(d, np.float32)
    if d == 0 or tot <= 0:
        return alias, prob
    q = ww * d / tot
    small = [i for i in range(d) if q[i] < 1.0]
    large = [i for i in range(d) if q[i] >= 1.0]
    while small and large:
        sm = small.pop()
        lg = large.pop()
        prob[sm] = q[sm]
        alias[sm] = lg
        q[lg] -= 1.0 - q[sm]
        (small if q[lg] < 1.0 else large).append(lg)
    for i in small + large:  # numerical leftovers: certain accept
        prob[i] = 1.0
        alias[i] = i
    return alias, prob


def _row_tables(ww: np.ndarray
                ) -> Tuple[np.ndarray, np.float32, np.ndarray, np.ndarray]:
    """(cdf, total, alias, prob) of ONE row from its float64 weights: the
    per-row definition of the tables (:func:`_table_rows` builds every
    row at once and is bit-identical to it)."""
    c = np.cumsum(ww)
    tot = c[-1] if c.shape[0] else 0.0
    alias, prob = _vose_row(ww, tot)
    return c.astype(np.float32), np.float32(tot), alias, prob


def _vose_lockstep(q: np.ndarray, d: np.ndarray, live: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Vose's construction run on every row of ``q`` ([n, D] float64,
    row r's first ``d[r]`` columns) at once, one stack pop per row per
    iteration.  Each row keeps its own small/large stacks (ascending at
    the start, popped from the top) and does exactly the float64
    operations :func:`_vose_row` does, in its order, so each row's
    (alias, prob) is bit-identical to the per-row build.  Rows not
    ``live`` (zero total) keep the neutral fill."""
    n, D = q.shape
    alias = np.zeros((n, D), np.int32)
    prob = np.ones((n, D), np.float32)
    cols = np.arange(D, dtype=np.int32)
    valid = (cols[None, :] < d[:, None]) & live[:, None]
    stacks, tops = [], []
    for member in (valid & (q < 1.0), valid & (q >= 1.0)):
        r, c = np.nonzero(member)  # row-major: ascending columns per row
        depth = np.cumsum(member, axis=1, dtype=np.int32)[r, c] - 1
        s = np.zeros((n, D), np.int32)
        s[r, depth] = c
        stacks.append(s.ravel())
        tops.append(member.sum(axis=1).astype(np.int64))
    sf, lf = stacks
    stop, ltop = tops
    qf, pf, af = q.ravel(), prob.ravel(), alias.ravel()
    base = np.arange(n, dtype=np.int64) * D
    act = np.nonzero((stop > 0) & (ltop > 0))[0]
    while act.size:
        b = base[act]
        st = b + stop[act] - 1  # flat positions of the stack tops
        lt = b + ltop[act] - 1
        sm = b + sf[st]
        lg = lf[lt]
        qs = qf[sm]
        pf[sm] = qs
        af[sm] = lg
        lg = b + lg
        ql = qf[lg] - (1.0 - qs)
        qf[lg] = ql
        # lg goes back on a stack: onto the small one in sm's place (the
        # large top shrinks), or it stays on top of the large one (the
        # small top shrinks)
        to_small = ql < 1.0
        sf[st[to_small]] = lg[to_small] - b[to_small]
        stop[act] -= ~to_small
        ltop[act] -= to_small
        act = act[(stop[act] > 0) & (ltop[act] > 0)]
    # numerical leftovers on either stack: certain accept, alias self
    for s, top in ((sf, stop), (lf, ltop)):
        left = cols[None, :] < top[:, None]
        r = np.nonzero(left)[0]
        c = s.reshape(n, D)[left]
        alias[r, c] = c
    return alias, prob


#: buckets with fewer rows are built row by row (:func:`_row_tables`): a
#: lock-step iteration costs about what 16 rows' per-row iterations do
_LOCKSTEP_MIN_ROWS = 16


def _table_rows(w: np.ndarray, starts: np.ndarray, degs: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(cdf, total, alias, prob) of every row of the flat float64 weights
    ``w`` (row v is ``w[starts[v]:starts[v] + degs[v]]``; per-edge outputs
    at the same positions), bit-identical to :func:`_row_tables` row by
    row.  Rows are bucketed by degree in powers of two; each bucket is
    one zero-padded [rows, D] block whose float64 prefix sums give the
    CDF and the totals, and whose alias tables :func:`_vose_lockstep`
    builds in lock-step, so the Python-level work is O(buckets + longest
    row) instead of O(rows + edges).  A bucket of fewer than
    ``_LOCKSTEP_MIN_ROWS`` rows (the few longest rows of a power-law
    graph) is built row by row, which costs less than its lock-step
    iterations would."""
    cdf = np.zeros(w.shape[0], np.float32)
    total = np.zeros(degs.shape[0], np.float32)
    alias = np.zeros(w.shape[0], np.int32)
    prob = np.ones(w.shape[0], np.float32)
    rows_nz = np.nonzero(degs > 0)[0]
    bucket = np.frexp(degs[rows_nz].astype(np.float64))[1]
    for k in np.unique(bucket):
        rows = rows_nz[bucket == k]
        if rows.size < _LOCKSTEP_MIN_ROWS:
            for v in rows:
                s = int(starts[v])
                e = s + int(degs[v])
                cdf[s:e], total[v], alias[s:e], prob[s:e] = \
                    _row_tables(w[s:e])
            continue
        d = degs[rows].astype(np.int64)
        D = int(d.max())
        cols = np.arange(D, dtype=np.int64)
        valid = cols[None, :] < d[:, None]
        pos = starts[rows].astype(np.int64)[:, None] + \
            np.minimum(cols[None, :], d[:, None] - 1)
        ww = np.where(valid, w[pos], 0.0)
        c = np.cumsum(ww, axis=1)
        tot = c[np.arange(rows.size), d - 1]
        live = tot > 0
        q = ww * d[:, None] / np.where(live, tot, 1.0)[:, None]
        a, p = _vose_lockstep(q, d, live)
        flat = pos[valid]
        cdf[flat] = c[valid]
        total[rows] = tot
        alias[flat] = a[valid]
        prob[flat] = p[valid]
    return cdf, total, alias, prob


def _vose_build(w: np.ndarray, indptr: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Vose alias tables for every CSR row (host-side, one-time
    preprocessing — not the per-step serial build the ALS baseline pays)."""
    indptr = np.asarray(indptr, np.int64)
    _, _, alias, prob = _table_rows(np.asarray(w, np.float64), indptr[:-1],
                                    np.diff(indptr))
    return alias, prob


def build_tables(graph: CSRGraph, workload: Workload, params,
                 aligned: bool = True) -> PrecompTables:
    """One-time table build for a static workload (host-side, row-local
    float64 accumulation so long rows keep full CDF precision; rows
    bucketed by degree and built a bucket at a time, :func:`_table_rows`).

    ``aligned`` additionally packs the tile-aligned [R, 128] kernel
    streams — required by the Pallas execution path, pure overhead
    (≈ 2× table memory + a repack) for engines pinned to the jnp
    selectors, which read only the flat arrays."""
    w = np.asarray(edge_weights_static(graph, workload, params), np.float64)
    indptr = np.asarray(graph.indptr, np.int64)
    V = graph.num_nodes
    if V and int(np.diff(indptr).max(initial=0)) >= (1 << 24):
        # alias offsets ride a float32 stream in the Pallas kernel layout
        raise ValueError("precomp tables require max degree < 2**24")
    cdf, total, alias, prob = _table_rows(w, indptr[:-1], np.diff(indptr))
    tables = PrecompTables(
        cdf=jnp.asarray(cdf),
        total=jnp.asarray(total),
        alias_off=jnp.asarray(alias),
        alias_prob=jnp.asarray(prob),
        invalid=jnp.zeros((V,), bool),
    )
    return tables.with_aligned(indptr) if aligned else tables


# ------------------------------------------------------ amortized rebuild
SCATTER_MODES = ("donate", "copy")


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_donate(dst, idx, vals):
    return dst.at[idx].set(vals)


@jax.jit
def _scatter_copy(dst, idx, vals):
    return dst.at[idx].set(vals)


def _scatter_rows(dst: jax.Array, idx: np.ndarray, vals: np.ndarray,
                  mode: str) -> jax.Array:
    """Jitted row scatter for the rebuild path: O(rows written), not the
    O(E) whole-table copy an unjitted ``.at[].set`` materialises.

    ``mode="donate"`` additionally donates ``dst`` so XLA writes in place
    — the caller's old table array is CONSUMED (every engine/queue call
    site reassigns the returned tables and never re-reads the old object,
    so this is the default); ``mode="copy"`` keeps the input alive (the
    fig12d before/after baseline, or callers that hold table snapshots).

    (idx, vals) are padded to the next power-of-two length by repeating
    the LAST entry — a duplicate scatter of an identical value is a
    deterministic no-op — so the jit cache holds O(log E) entries per
    dtype instead of one per drain size.
    """
    idx = np.asarray(idx)
    if idx.size == 0:
        return dst
    n = idx.shape[0]
    m = max(1, 1 << (n - 1).bit_length())
    if m != n:
        idx = np.concatenate([idx, np.full(m - n, idx[-1], idx.dtype)])
        vals = np.concatenate(
            [vals, np.broadcast_to(vals[-1:], (m - n,) + vals.shape[1:])])
    fn = _scatter_donate if mode == "donate" else _scatter_copy
    return fn(dst, jnp.asarray(idx, jnp.int32), jnp.asarray(vals))


def rebuild_rows(tables: PrecompTables, graph: CSRGraph, workload: Workload,
                 params, nodes, *, scatter: str = "donate") -> PrecompTables:
    """Re-bake the listed nodes' rows from the CURRENT graph weights and
    flip their validity bits back.

    Bit-identity contract (pinned by tests/test_rebuild.py): a rebuilt row
    equals the row :func:`build_tables` of the same graph would produce —
    the per-edge weight evaluation and the float64 table math
    (:func:`_table_rows`) are the same code paths — so draining every
    stale row restores exactly the fresh-build tables.  Rows are
    disjoint, so rebuild order is irrelevant.  Updates both the flat
    arrays and (when present) the tile-aligned kernel streams; all shapes
    are preserved, so the jitted epoch closed over the *structure* never
    retraces.

    ``scatter`` selects the write path (see :func:`_scatter_rows`): the
    default ``"donate"`` updates the tables in place — O(rows) per drain
    instead of O(E) — and consumes the INPUT ``tables``' buffers, which
    must not be read afterwards; ``"copy"`` preserves them.
    """
    if scatter not in SCATTER_MODES:
        raise ValueError(f"scatter {scatter!r} not one of {SCATTER_MODES}")
    nodes_arr = np.unique(np.atleast_1d(np.asarray(nodes, np.int64)))
    if nodes_arr.size == 0:
        return tables
    # row layout through the shared helper, so rebuilds work on both the
    # contiguous CSR and a delta-overlay graph (whose touched rows live
    # in the patch region)
    starts_all, deg_all = host_row_layout(graph)
    degs = deg_all[nodes_arr]
    edge_idx = np.concatenate(
        [np.arange(starts_all[v], starts_all[v] + deg_all[v])
         for v in nodes_arr]
    ) if degs.sum() else np.zeros(0, np.int64)
    bounds = np.zeros(nodes_arr.size + 1, np.int64)
    np.cumsum(degs, out=bounds[1:])

    if edge_idx.size:
        src = np.repeat(nodes_arr, degs)
        w = np.asarray(_eval_static_weights(
            graph, workload, params,
            jnp.asarray(edge_idx, jnp.int32),
            jnp.asarray(src, jnp.int32),
            jnp.asarray(deg_all[src], jnp.int32)), np.float64)
    else:
        w = np.zeros(0, np.float64)

    new_cdf, new_total, new_alias, new_prob = _table_rows(w, bounds[:-1],
                                                          degs)

    out = dataclasses.replace(
        tables,
        cdf=_scatter_rows(tables.cdf, edge_idx, new_cdf, scatter),
        total=_scatter_rows(tables.total, nodes_arr, new_total, scatter),
        alias_off=_scatter_rows(tables.alias_off, edge_idx, new_alias,
                                scatter),
        alias_prob=_scatter_rows(tables.alias_prob, edge_idx, new_prob,
                                 scatter),
        invalid=_scatter_rows(tables.invalid, nodes_arr,
                              np.zeros(nodes_arr.size, bool), scatter),
    )
    if tables.arow0 is None:
        return out
    # aligned streams: each node owns rows [arow0, arow0 + ⌈d/128⌉) of the
    # [R, 128] layout exclusively, zero-padded past its degree — writing
    # the full zero-padded span reproduces align_rows' fill exactly.
    from repro.kernels.ref import LANES

    arow0 = np.asarray(tables.arow0, np.int64)
    rows: List[np.ndarray] = []
    blk_cdf: List[np.ndarray] = []
    blk_prob: List[np.ndarray] = []
    blk_alias: List[np.ndarray] = []
    for i, v in enumerate(nodes_arr):
        d = int(degs[i])
        nrows = (d + LANES - 1) // LANES
        if nrows == 0:
            continue
        s, e = int(bounds[i]), int(bounds[i + 1])
        for blocks, vals in ((blk_cdf, new_cdf[s:e]),
                             (blk_prob, new_prob[s:e]),
                             (blk_alias, new_alias[s:e].astype(np.float32))):
            buf = np.zeros(nrows * LANES, np.float32)
            buf[:d] = vals
            blocks.append(buf.reshape(nrows, LANES))
        rows.append(arow0[v] + np.arange(nrows))
    if not rows:
        return out
    ridx = np.concatenate(rows)
    return dataclasses.replace(
        out,
        cdf2d=_scatter_rows(tables.cdf2d, ridx, np.concatenate(blk_cdf),
                            scatter),
        prob2d=_scatter_rows(tables.prob2d, ridx, np.concatenate(blk_prob),
                             scatter),
        alias2d=_scatter_rows(tables.alias2d, ridx,
                              np.concatenate(blk_alias), scatter),
    )


def splice_tables(tables: PrecompTables, old_starts, old_degs,
                  new_starts, new_degs, new_len: int) -> PrecompTables:
    """Re-layout the per-edge table values onto a new row layout — the
    O(E) gather behind structural updates and overlay compaction.

    Rows whose degree is unchanged move wholesale (their values are a
    pure function of the row's weights, not of where the row lives, so a
    moved row stays bit-identical); rows whose degree changed get the
    fresh-build neutral fill and MUST be invalidated by the caller — the
    rebuild queue re-bakes them with real values.  Per-node arrays
    (``total`` / ``invalid``) are layout-independent and carry over.
    The tile-aligned kernel streams are dropped (their geometry is
    topology-bound); re-attach with :meth:`PrecompTables.with_aligned`
    after a compaction when a Pallas path needs them.
    """
    old_starts = np.asarray(old_starts, np.int64)
    old_degs = np.asarray(old_degs, np.int64)
    new_starts = np.asarray(new_starts, np.int64)
    new_degs = np.asarray(new_degs, np.int64)
    V = old_starts.shape[0]
    copy_deg = np.where(old_degs == new_degs, new_degs, 0)
    n = int(copy_deg.sum())
    src_rows = np.repeat(np.arange(V, dtype=np.int64), copy_deg)
    bounds = np.zeros(V + 1, np.int64)
    np.cumsum(copy_deg, out=bounds[1:])
    within = np.arange(n, dtype=np.int64) - np.repeat(bounds[:-1], copy_deg)
    gather = old_starts[src_rows] + within
    scatter = new_starts[src_rows] + within

    def move(arr, fill, dtype):
        out = np.full(int(new_len), fill, dtype)
        if n:
            out[scatter] = np.asarray(arr)[gather]
        return jnp.asarray(out)

    return PrecompTables(
        cdf=move(tables.cdf, 0.0, np.float32),
        total=tables.total,
        alias_off=move(tables.alias_off, 0, np.int32),
        alias_prob=move(tables.alias_prob, 1.0, np.float32),
        invalid=tables.invalid,
    )


def grow_tables(tables: PrecompTables, new_len: int) -> PrecompTables:
    """Keep the per-edge tables in the *overlay* layout across an
    ``apply_updates`` — the O(touched) replacement for running
    :func:`splice_tables` on every structural edit.

    While a delta overlay is active the table arrays are addressed
    through the overlay's ``row_starts``/``row_degs``, and the overlay's
    patch allocator keeps every row's span stable between compactions —
    so a valid row's table values are *already* at the right offsets and
    the only thing an apply has to do is extend the arrays to the new
    edge-array length (base + patch capacity).  Capacities are powers of
    two, so the O(E) concatenate here runs O(log) times per compaction
    cycle and this is an O(1) no-op on every other apply; the one-shot
    O(E) re-layout back to the contiguous order is deferred to
    ``WalkEngine.compact()`` (which still uses :func:`splice_tables`).

    Newly exposed positions get the fresh-build neutral fill (cdf 0.0,
    alias_off 0, alias_prob 1.0) and are only ever read after
    ``rebuild_rows`` wrote real values — callers invalidate the touched
    rows, exactly like the splice path.  Per-node arrays (``total`` /
    ``invalid``) are layout-independent and carry over.  The
    tile-aligned kernel streams are ALWAYS dropped, even when the length
    is unchanged: their geometry is bound to the pre-mutation topology,
    and serving a kernel DMA from a stale stream would be a silent wrong
    draw (``precomp_table_select`` guards against a partial layout).
    """
    cur = int(tables.cdf.shape[0])
    new_len = int(new_len)
    if new_len < cur:
        raise ValueError(
            f"grow_tables cannot shrink: tables hold {cur} edge slots, "
            f"overlay asks for {new_len} — compaction goes through "
            f"splice_tables")
    out = tables
    if (tables.cdf2d is not None or tables.prob2d is not None
            or tables.alias2d is not None or tables.arow0 is not None):
        out = dataclasses.replace(out, cdf2d=None, prob2d=None,
                                  alias2d=None, arow0=None)
    if new_len == cur:
        return out
    ext = new_len - cur
    return dataclasses.replace(
        out,
        cdf=jnp.concatenate(
            [out.cdf, jnp.zeros((ext,), out.cdf.dtype)]),
        alias_off=jnp.concatenate(
            [out.alias_off, jnp.zeros((ext,), out.alias_off.dtype)]),
        alias_prob=jnp.concatenate(
            [out.alias_prob, jnp.ones((ext,), out.alias_prob.dtype)]),
    )


class RebuildQueue:
    """Host-side FIFO of stale table rows awaiting amortized rebuild.

    The engine pushes every node ``update_graph`` invalidates and drains a
    budgeted few rows per scheduler epoch (between jitted epochs, where
    host work is free) — so a weight mutation costs one bitmap write now
    and O(row) rebuild work spread over the following epochs, instead of
    demoting the row to the dynamic path forever.  Deliberately not a
    pytree: it never enters a traced computation.

    Invariant (pinned by the tests/test_rebuild.py property suite): when
    all invalidation flows through :meth:`push`, the queue's membership is
    exactly the set of ``True`` bits in ``PrecompTables.invalid`` — a row
    is pending iff it is stale, and a fully drained queue means a fully
    valid bitmap.
    """

    def __init__(self):
        self._pending: deque = deque()
        self._member: set = set()

    def push(self, nodes) -> int:
        """Enqueue stale rows (deduplicated; re-invalidating a pending row
        is a no-op — its eventual rebuild reads the latest graph anyway).
        Returns how many rows were newly enqueued."""
        added = 0
        for v in np.atleast_1d(np.asarray(nodes, np.int64)).tolist():
            if v not in self._member:
                self._member.add(v)
                self._pending.append(v)
                added += 1
        return added

    def __len__(self) -> int:
        return len(self._pending)

    def pending(self) -> Tuple[int, ...]:
        return tuple(self._pending)

    def drain(self, tables: PrecompTables, graph: CSRGraph,
              workload: Workload, params, budget: Optional[int] = None,
              scatter: str = "donate") -> Tuple[PrecompTables, List[int]]:
        """Rebuild up to ``budget`` queued rows (all of them when None).
        Returns (new tables, the rows rebuilt).  ``scatter`` follows
        :func:`rebuild_rows`: the default donates the old tables' buffers
        to the in-place row scatter, so callers must adopt the returned
        tables and drop the input object (every engine call site does)."""
        n = len(self._pending) if budget is None \
            else min(int(budget), len(self._pending))
        if n <= 0:
            return tables, []
        nodes = [self._pending.popleft() for _ in range(n)]
        self._member.difference_update(nodes)
        return rebuild_rows(tables, graph, workload, params, nodes,
                            scatter=scatter), nodes


# ----------------------------------------------------------- jnp selectors
def its_select(graph: CSRGraph, tables: PrecompTables, cur: jax.Array,
               rng: jax.Array, *, active: jax.Array,
               depth: int = INT32_DEPTH) -> jax.Array:
    """O(log d) inverse-transform draw from the baked CDF.

    ``u·total`` → fixed-depth binary search for the first row offset whose
    inclusive prefix exceeds the target (zero-weight neighbours share the
    previous prefix value, so they can never be landed on).  ``depth``
    bounds the halvings (see :func:`repro.graphs.csr.search_depth`; the
    default covers any int32 degree).  The uniform comes from the
    counter-based Threefry stream (:func:`threefry_seeds` +
    ``ITS_SALT``) — the same draw the Pallas ``its_search`` kernel
    makes, so both paths pick the same offset.  Returns next nodes [W];
    -1 for inactive / empty / zero-total lanes.
    """
    E = graph.num_edges
    deg = degrees_of(graph, cur)
    vs = jnp.maximum(cur, 0)
    start = graph.row_starts(vs)
    seeds = threefry_seeds(rng)
    u = uniform_01(seeds[:, 0], seeds[:, 1], jnp.uint32(0),
                   jnp.uint32(ITS_SALT))
    total = tables.total[vs]
    target = u * total

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        val = tables.cdf[jnp.clip(start + mid, 0, E - 1)]
        go_right = (val <= target) & (lo < hi)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(go_right | (lo >= hi), hi, mid)
        return (new_lo, new_hi)

    lo0 = jnp.zeros_like(deg)
    lo, _ = jax.lax.fori_loop(0, depth, body, (lo0, deg))
    sel = jnp.clip(lo, 0, jnp.maximum(deg - 1, 0))
    nxt = graph.indices[jnp.clip(start + sel, 0, E - 1)]
    ok = active & (deg > 0) & (total > 0)
    return jnp.where(ok, nxt, -1)


def alias_select(graph: CSRGraph, tables: PrecompTables, cur: jax.Array,
                 rng: jax.Array, *, active: jax.Array) -> jax.Array:
    """O(1) alias draw: column = ⌊u₁·d⌋, keep it iff u₂ < prob, else take
    its alias partner.  Uniforms come from the shared Threefry stream
    (``ALIAS_SALT``), matching the Pallas ``alias_pick`` kernel draw for
    draw.  Returns next nodes [W]; -1 as in its_select."""
    E = graph.num_edges
    deg = degrees_of(graph, cur)
    vs = jnp.maximum(cur, 0)
    start = graph.row_starts(vs)
    seeds = threefry_seeds(rng)
    u1, u2 = uniform_pair_01(seeds[:, 0], seeds[:, 1], jnp.uint32(0),
                             jnp.uint32(ALIAS_SALT))
    col = jnp.minimum((u1 * deg.astype(jnp.float32)).astype(jnp.int32),
                      jnp.maximum(deg - 1, 0))
    pos = jnp.clip(start + col, 0, E - 1)
    p_col = tables.alias_prob[pos]
    a_col = tables.alias_off[pos]
    sel = jnp.where(u2 < p_col, col, a_col)
    nxt = graph.indices[jnp.clip(start + sel, 0, E - 1)]
    ok = active & (deg > 0) & (tables.total[vs] > 0)
    return jnp.where(ok, nxt, -1)
