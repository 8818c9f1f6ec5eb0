"""eRVS — enhanced reservoir sampling (paper §3.2, Alg. 1 + Fig. 4).

Two statistically equivalent implementations:

* :func:`ervs_step` — the EXP optimisation: Efraimidis–Spirakis exponential
  keys, arg-max selection.  No prefix sum over the weights (the baseline
  FlowWalker kernel needs one) — a single streaming pass.
  We use the *log-domain* key ln(u)/w̃ (monotone in u^{1/w̃}); the float key
  of the paper underflows fp32 for small w̃, the log form does not.
* :func:`ervs_jump_step` — adds the A-ExpJ *jump* technique [9, 16]: per
  lane, a threshold T drawn once replaces per-neighbour RNG; random numbers
  are only drawn when the cumulative weight crosses T.  Statistically
  identical; the point is the RNG/transcendental reduction, which the Pallas
  kernel exploits at block granularity (see kernels/ervs_kernel.py).

Both scan the neighbour list in [W, tile] blocks with a fori_loop, so memory
traffic is one streaming pass over each walker's row — the paper's "roughly
halves the costly memory accesses" claim vs prefix-sum RVS.  Every lane runs
every trip, so a pass costs ``W × tile`` lane-slots per trip however few of
them hold a neighbour; :func:`tile_pass` gives a pass's trip count (the loop
bound both functions use) and the neighbour entries it reads, which the
samplers report as the ``ervs_trips`` / ``ervs_edges`` step counters, and
``trips × W`` as ``ervs_lane_trips``.

:func:`compact_lanes` runs a pass over a sparse partition's active lanes
only, ``LANE_CHUNK`` of them at a time: a lane's choice reads nothing but
its own row, keys and state, so the compacted pass picks exactly what the
dense one would.

Engine integration: registered as the ``ervs`` / ``ervs_jump`` samplers
(``samplers.ERVSSampler`` / ``ERVSJumpSampler``); both honour the runtime
partition mask, so either can serve as the reservoir half of a
``PartitionedSampler``, which runs them through :func:`compact_lanes`.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ctxutil import degrees_of as degrees_of_cached, eval_weights, tile_ctx
from repro.core.types import Workload
from repro.graphs.csr import INT32_DEPTH, CSRGraph, search_depth
from repro.kernels.prng import threefry_seeds, tile_uniforms

NEG_INF = jnp.float32(-jnp.inf)

#: lanes per shard in one chunk of :func:`compact_lanes`: two 128-lane
#: vregs, above the reservoir lanes a node2vec step leaves after the cost
#: model's split on a power-law graph, so one chunk is the normal case.
#: Read when the enclosing program is traced.
LANE_CHUNK = 256


def tile_pass(graph: CSRGraph, cur: jax.Array, active: jax.Array, tile: int,
              max_tiles: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """(trips, edges) of one tile-loop pass over the ``active`` lanes.

    ``trips`` is the loop bound: tiles needed by the longest active row,
    capped at ``max_tiles``.  ``edges`` is the neighbour entries the pass
    reads, ``Σ min(degree, trips·tile)`` over the active lanes, so
    ``edges <= trips · tile · W`` always.  Both are int32 scalars, and a
    max and a sum over the lanes — exact under a sharded lane axis.
    """
    deg_act = jnp.where(active, degrees_of_cached(graph, cur), 0)
    trips = (jnp.max(deg_act) + tile - 1) // tile
    if max_tiles is not None:
        trips = jnp.minimum(trips, max_tiles)
    edges = jnp.sum(jnp.minimum(deg_act, trips * tile), dtype=jnp.int32)
    return trips.astype(jnp.int32), edges


def pass_depth(tile: int, max_tiles: Optional[int]) -> int:
    """Depth of the ``dist`` search in a pass of at most ``max_tiles``
    trips: ``max_tiles · tile`` (the engine's ``pad`` for a power-of-two
    tile) bounds every row, a previous node's too; uncapped, any int32
    row."""
    if max_tiles is None:
        return INT32_DEPTH
    return search_depth(max_tiles * tile)


def compact_lanes(pass_fn: Callable, lanes, active: jax.Array,
                  shards: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Run a reservoir pass over the ``active`` lanes only.

    ``pass_fn(lanes, active) -> (next [n], trips)`` is the pass over ``n``
    lanes, ``lanes`` a pytree of per-lane arrays (leading dim W).  The
    slot axis is viewed as ``[shards, W // shards]`` and each shard's
    active lanes are taken in ascending slot order, ``K = min(LANE_CHUNK,
    W // shards)`` per shard at a time: a chunk runs ``pass_fn`` on its
    ``shards · K`` gathered lanes and its picks are scattered back to
    their slots.  Every gather and scatter stays inside its shard, so a
    pool sharded over ``shards`` devices compacts with no traffic but the
    scalar max behind the chunk count, ``ceil(largest shard's active
    count / K)``, a ``while`` bound.  Where one chunk would hold a whole
    shard, the dense pass runs as it is.

    Returns (next [W], -2 on inactive lanes; lane-trips, each chunk's
    trips times the lanes of its tile, summed).
    """
    W = active.shape[0]
    spd = W // shards
    K = min(LANE_CHUNK, spd)
    if K == spd:
        nxt, trips = pass_fn(lanes, active)
        return jnp.where(active, nxt, -2), trips * W
    act = active.reshape(shards, spd)
    count = jnp.sum(act, axis=1, dtype=jnp.int32)
    # each shard's active slots first, in ascending order, padded to whole
    # chunks (a padding column is never valid)
    order = jnp.argsort(~act, axis=1, stable=True).astype(jnp.int32)
    order = jnp.pad(order, ((0, 0), (0, -(-spd // K) * K - spd)))
    n_chunks = (jnp.max(count) + K - 1) // K
    by_shard = jax.tree_util.tree_map(
        lambda x: x.reshape((shards, spd) + x.shape[1:]), lanes)

    def gather(x, idx):
        return jax.vmap(lambda xs, i: xs[i])(x, idx).reshape(
            (shards * K,) + x.shape[2:])

    def body(c, carry):
        out, lane_trips = carry
        idx = jax.lax.dynamic_slice_in_dim(order, c * K, K, axis=1)
        valid = (c * K + jnp.arange(K, dtype=jnp.int32))[None, :] \
            < count[:, None]
        nxt, trips = pass_fn(
            jax.tree_util.tree_map(lambda x: gather(x, idx), by_shard),
            valid.reshape(-1))
        dest = jnp.where(valid, idx, spd)  # out of range: dropped
        out = jax.vmap(lambda o, i, v: o.at[i].set(v, mode="drop"))(
            out, dest, nxt.reshape(shards, K))
        return out, lane_trips + trips * (shards * K)

    out, lane_trips = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.full((shards, spd), -2, jnp.int32), jnp.int32(0)))
    return out.reshape(W), lane_trips


def _log_keys(u: jax.Array, w: jax.Array) -> jax.Array:
    """ln(key) = ln(u)/w̃ for w̃>0 else -inf.  u ∈ (0,1)."""
    safe_w = jnp.where(w > 0, w, 1.0)
    lk = jnp.log(u) / safe_w
    return jnp.where(w > 0, lk, NEG_INF)


@partial(jax.jit, static_argnames=("workload", "params", "tile", "max_tiles"))
def ervs_step(
    graph: CSRGraph,
    workload: Workload,
    params,
    cur: jax.Array,
    prev: jax.Array,
    step: jax.Array,
    rng: jax.Array,  # [W, 2] per-walker keys
    tile: int = 256,
    max_tiles: Optional[int] = None,
    active: Optional[jax.Array] = None,
    wstate=None,
) -> jax.Array:
    """One eRVS step for a batch of walkers.  Returns next nodes [W] (or -1).

    ``active`` masks walkers this kernel should process (runtime partition);
    inactive walkers return -2 (untouched sentinel for the engine to merge).
    ``wstate`` is the per-walker program state fed to ``get_weight``
    (WalkProgram contract); ``None`` for stateless programs.
    ``max_tiles · tile`` must bound every row: it sets the depth of the
    ``dist`` search (:func:`pass_depth`).
    """
    W = cur.shape[0]
    if active is None:
        active = jnp.ones((W,), bool)
    depth = pass_depth(tile, max_tiles)
    # dynamic trip count: tiles needed by the *active* partition only — when
    # the cost model sends every high-degree walker to eRJS, the eRVS pass
    # shrinks accordingly (fori_loop with a traced bound lowers to while).
    needed, _ = tile_pass(graph, cur, active, tile, max_tiles)

    def body(t, carry):
        best_lk, best_nbr = carry
        ctx, mask = tile_ctx(graph, workload, cur, prev, step,
                             jnp.full((W,), t * tile, jnp.int32), tile,
                             depth=depth)
        w = eval_weights(workload, params, ctx, mask, wstate)
        # counter-based per-(walker, tile) uniforms — the "jumping RNG" idiom:
        # no sequential stream to advance, so tiles are independent.
        u = _tile_uniforms(rng, t, (W, tile))
        lk = jnp.where(mask & active[:, None], _log_keys(u, w), NEG_INF)
        tile_best = jnp.argmax(lk, axis=1)
        tile_lk = jnp.take_along_axis(lk, tile_best[:, None], axis=1)[:, 0]
        tile_nbr = jnp.take_along_axis(ctx.nbr, tile_best[:, None], axis=1)[:, 0]
        upd = tile_lk > best_lk
        return (jnp.where(upd, tile_lk, best_lk), jnp.where(upd, tile_nbr, best_nbr))

    init = (jnp.full((W,), NEG_INF), jnp.full((W,), -1, jnp.int32))
    best_lk, best_nbr = jax.lax.fori_loop(0, needed, body, init)
    return jnp.where(active, best_nbr, -2)


@partial(jax.jit, static_argnames=("workload", "params", "tile", "max_tiles"))
def ervs_jump_step(
    graph: CSRGraph,
    workload: Workload,
    params,
    cur: jax.Array,
    prev: jax.Array,
    step: jax.Array,
    rng: jax.Array,
    tile: int = 256,
    max_tiles: Optional[int] = None,
    active: Optional[jax.Array] = None,
    wstate=None,
) -> jax.Array:
    """A-ExpJ (jump) variant.  Returns next nodes [W] (or -1; -2 inactive).

    Each *lane* l ∈ [0, tile) owns the strided neighbour subsequence
    {l, l+tile, l+2·tile, …} of its walker, runs sequential A-ExpJ on it
    (carry: local log-key max, threshold, cumulative weight), and the final
    reduction arg-maxes over lanes — exactly the paper's per-thread local
    max + cross-thread reduction (Fig. 4b), with threads → vector lanes.

    On SIMD hardware the arithmetic cost of a masked lane is not saved, so
    this function is the semantic oracle of the jump technique.  The
    block-jump kernel (kernels/ervs_kernel.py) skips whole *blocks*; it and
    its reference ``kernels/ref.py:ervs_select_ref`` count the draws they
    consume, the Fig. 12a JUMP ablation's statistics source.
    ``max_tiles`` is as in :func:`ervs_step`.
    """
    W = cur.shape[0]
    if active is None:
        active = jnp.ones((W,), bool)
    depth = pass_depth(tile, max_tiles)
    needed, _ = tile_pass(graph, cur, active, tile, max_tiles)

    def body(t, carry):
        lk_max, nbr_best, thresh, cumw = carry
        ctx, mask = tile_ctx(graph, workload, cur, prev, step,
                             jnp.full((W,), t * tile, jnp.int32), tile,
                             depth=depth)
        w = eval_weights(workload, params, ctx, mask, wstate)  # [W, tile]
        w = jnp.where(active[:, None], w, 0.0)
        is_first = lk_max == NEG_INF  # lane not initialised yet
        # --- initialisation: first item of each lane draws a plain key ---
        u0 = _tile_uniforms(rng, 2 * t, (W, tile))
        init_lk = _log_keys(u0, w)
        # --- jump: does this item cross the lane threshold? ---
        crossed = (cumw + w >= thresh) & (w > 0) & mask
        # conditional key on crossing: u2 ~ U(t_w, 1), t_w = exp(w·lk_max)
        t_w = jnp.exp(jnp.clip(w * lk_max, -80.0, 0.0))
        u2 = t_w + u0 * (1.0 - t_w)
        cross_lk = _log_keys(jnp.clip(u2, 1e-38, 1.0), w)
        new_key = jnp.where(is_first, init_lk, cross_lk)
        take = (is_first & (w > 0) & mask) | crossed
        # new threshold after an update: T = ln(u')/lk_new, cumw resets
        u1 = _tile_uniforms(rng, 2 * t + 1, (W, tile))
        lk_new = jnp.where(take, new_key, lk_max)
        new_thresh_val = jnp.log(u1) / jnp.where(lk_new < 0, lk_new, -1e-30)
        thresh = jnp.where(take, new_thresh_val, thresh)
        cumw = jnp.where(take, 0.0, cumw + jnp.where(mask, w, 0.0))
        nbr_best = jnp.where(take, ctx.nbr, nbr_best)
        return (lk_new, nbr_best, thresh, cumw)

    init = (
        jnp.full((W, tile), NEG_INF),
        jnp.full((W, tile), -1, jnp.int32),
        jnp.zeros((W, tile), jnp.float32),  # thresh: first item always "crosses" via is_first
        jnp.zeros((W, tile), jnp.float32),
    )
    lk, nbr, _, _ = jax.lax.fori_loop(0, needed, body, init)
    lane = jnp.argmax(lk, axis=1)
    best = jnp.take_along_axis(nbr, lane[:, None], axis=1)[:, 0]
    best = jnp.where(jnp.max(lk, axis=1) > NEG_INF, best, -1)
    return jnp.where(active, best, -2)


def _tile_uniforms(rng: jax.Array, t, shape) -> jax.Array:
    """Counter-based uniforms for (walker-batch, tile t): counter (t, j).

    rng is [W] per-walker keys; any tile's randomness is addressable
    without advancing a stream — this is what makes block-level jumps
    actually free in the Pallas kernel, which calls the same
    ``prng.tile_uniforms`` per lane (bit-identical by construction).
    """
    W, tile = shape
    seeds = threefry_seeds(rng)
    j = jnp.arange(tile, dtype=jnp.uint32)[None, :]
    return tile_uniforms(seeds[:, :1], seeds[:, 1:], t, j)
