"""Baseline sampling methods (paper §2.2, Fig. 2) — the comparison systems.

These are faithful JAX ports of the *algorithms* used by the published
baselines, with their characteristic costs preserved:

* ALS  (alias sampling, Skywalker):   O(d) sequential table build per step,
  then O(1) draws.  The build is the sequential two-stack Vose algorithm —
  its serial dependence is the cost the paper's Fig. 3 exposes.
* ITS  (inverse transform, C-SAW):    prefix sum + binary search.
* RVS  (prefix-sum reservoir, FlowWalker): prefix sum + per-neighbour
  uniform + parallel last-accept reduction.
* RJS  (max-reduce rejection, NextDoor): full-row max reduction, then
  rejection trials — the max reduction is what eRJS eliminates.

All operate on one [W, D] padded block (D = padded max degree of the batch);
that padding is itself representative of how the GPU baselines bucket work.
Each is registered with the sampler registry via ``samplers.
PaddedRowSampler`` (see :data:`BASELINE_STEP_FNS`); none supports runtime
partitioning — the full-row pass is exactly the cost they exist to expose.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ctxutil import degrees_of, tile_ctx, eval_weights
from repro.core.erjs import erjs_step
from repro.core.types import Workload
from repro.graphs.csr import CSRGraph, search_depth


def padded_weights(
    graph: CSRGraph, workload: Workload, params,
    cur, prev, step, pad: int, wstate=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-row transition weights, padded to [W, pad].  Returns (w, nbr, mask).

    ``wstate`` is the per-walker program state ([W]-leading leaves;
    ``None`` for stateless programs).  ``pad`` bounds every row, a
    previous node's too: it sets the ``dist`` search's depth."""
    ctx, mask = tile_ctx(graph, workload, cur, prev, step,
                         jnp.zeros_like(cur), pad, depth=search_depth(pad))
    w = eval_weights(workload, params, ctx, mask, wstate)
    return w, ctx.nbr, mask


# ---------------------------------------------------------------- ITS (C-SAW)
@partial(jax.jit, static_argnames=("workload", "params", "pad"))
def its_step(graph, workload: Workload, params, cur, prev, step, rng, pad: int,
             wstate=None):
    w, nbr, mask = padded_weights(graph, workload, params, cur, prev, step,
                                  pad, wstate)
    csum = jnp.cumsum(w, axis=1)
    total = csum[:, -1]
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (), dtype=jnp.float32))(rng)
    r = u * total
    # first index with csum > r  (strictly: right bisect)
    sel = jnp.sum((csum <= r[:, None]).astype(jnp.int32), axis=1)
    sel = jnp.minimum(sel, pad - 1)
    out = jnp.take_along_axis(nbr, sel[:, None], axis=1)[:, 0]
    return jnp.where(total > 0, out, -1)


# ----------------------------------------------------- prefix-RVS (FlowWalker)
@partial(jax.jit, static_argnames=("workload", "params", "pad"))
def rvs_prefix_step(graph, workload: Workload, params, cur, prev, step, rng,
                    pad: int, wstate=None):
    """FlowWalker's parallel reservoir: accept_i iff u_i < w_i / W_i, where
    W_i is the inclusive prefix sum; the *last* accepting index wins (this is
    the parallelisation of sequential reservoir sampling the paper describes
    in §2.2 — prefix sum + per-neighbour RNG + max-index reduction)."""
    w, nbr, mask = padded_weights(graph, workload, params, cur, prev, step,
                                  pad, wstate)
    W_i = jnp.cumsum(w, axis=1)
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (pad,), dtype=jnp.float32, minval=1e-12))(rng)
    ok = (u * W_i < w) & mask & (w > 0)
    idx = jnp.arange(pad, dtype=jnp.int32)[None, :]
    last = jnp.max(jnp.where(ok, idx, -1), axis=1)
    out = jnp.take_along_axis(nbr, jnp.maximum(last, 0)[:, None], axis=1)[:, 0]
    return jnp.where(last >= 0, out, -1)


# ------------------------------------------------------ max-reduce RJS (NextDoor)
@partial(jax.jit, static_argnames=("workload", "params", "pad", "trials_per_round", "max_rounds"))
def rjs_maxreduce_step(graph, workload: Workload, params, cur, prev, step, rng,
                       pad: int, trials_per_round: int = 8, max_rounds: int = 64,
                       wstate=None):
    """NextDoor-style: pay a full-row pass for the exact max, then trials.
    The full pass is the cost eRJS's bound estimation removes."""
    w, _, _ = padded_weights(graph, workload, params, cur, prev, step, pad,
                             wstate)
    exact_max = jnp.max(w, axis=1)
    nxt, fb, _ = erjs_step(graph, workload, params, cur, prev, step, rng,
                           bound=exact_max, trials_per_round=trials_per_round,
                           max_rounds=max_rounds, wstate=wstate,
                           depth=search_depth(pad))
    # exact max ⇒ acceptance ≥ 1/d; fall back to ITS on the (rare) unresolved
    its = its_step(graph, workload, params, cur, prev, step, rng, pad,
                   wstate=wstate)
    return jnp.where(fb, its, nxt)


# ---------------------------------------------------------------- ALS (Skywalker)
@partial(jax.jit, static_argnames=("workload", "params", "pad"))
def als_step(graph, workload: Workload, params, cur, prev, step, rng, pad: int,
             wstate=None):
    """Alias sampling with per-step table (re)construction (Skywalker
    extended to dynamic walks): Vose two-stack build — O(d) with a *serial*
    dependence chain, which is exactly the per-step overhead Fig. 3 exposes.

    The build runs the textbook Vose algorithm with explicit stacks inside a
    fori_loop (each iteration finalises one "small" entry, so ``pad``
    iterations always suffice); padded lanes never enter the stacks.
    """
    w, nbr, mask = padded_weights(graph, workload, params, cur, prev, step,
                                  pad, wstate)
    deg = degrees_of(graph, cur)
    total = jnp.sum(w, axis=1)

    def build_one(w_row, deg_row, total_row):
        lane = jnp.arange(pad, dtype=jnp.int32)
        valid = lane < deg_row
        n = jnp.maximum(deg_row, 1).astype(jnp.float32)
        q = jnp.where(valid, w_row * n / jnp.maximum(total_row, 1e-30), 1.0)
        is_small = (q < 1.0) & valid
        is_large = (q >= 1.0) & valid
        # initial stacks: valid lanes of each class, compacted to the front.
        small_stack = jnp.sort(jnp.where(is_small, lane, pad))
        large_stack = jnp.sort(jnp.where(is_large, lane, pad))
        small_top = jnp.sum(is_small.astype(jnp.int32))
        large_top = jnp.sum(is_large.astype(jnp.int32))
        alias0 = lane
        prob0 = jnp.ones((pad,), jnp.float32)

        def body(_, st):
            q, alias, prob, s_stk, s_top, l_stk, l_top = st
            can = (s_top > 0) & (l_top > 0)
            s = s_stk[jnp.clip(s_top - 1, 0, pad - 1)]
            l = l_stk[jnp.clip(l_top - 1, 0, pad - 1)]
            # finalise small s against large l
            prob = jnp.where(can, prob.at[s].set(q[s]), prob)
            alias = jnp.where(can, alias.at[s].set(l), alias)
            new_ql = q[l] - (1.0 - q[s])
            q = jnp.where(can, q.at[l].set(new_ql), q)
            s_top = s_top - can.astype(jnp.int32)
            # l demoted to small when its residual drops below 1
            demote = can & (new_ql < 1.0)
            l_top = l_top - demote.astype(jnp.int32)
            s_stk = jnp.where(demote, s_stk.at[jnp.clip(s_top, 0, pad - 1)].set(l), s_stk)
            s_top = s_top + demote.astype(jnp.int32)
            return (q, alias, prob, s_stk, s_top, l_stk, l_top)

        st = (q, alias0, prob0, small_stack, small_top, large_stack, large_top)
        _, alias, prob, _, _, _, _ = jax.lax.fori_loop(0, pad, body, st)
        return alias, prob

    alias, prob = jax.vmap(build_one)(w, deg, total)
    # draw: 2 uniforms → (column, accept-or-alias)
    k1 = jax.vmap(lambda k: jax.random.uniform(
        k, (2,), dtype=jnp.float32))(rng)
    col = jnp.minimum((k1[:, 0] * deg.astype(jnp.float32)).astype(jnp.int32),
                      jnp.maximum(deg - 1, 0))
    p_col = jnp.take_along_axis(prob, col[:, None], axis=1)[:, 0]
    a_col = jnp.take_along_axis(alias, col[:, None], axis=1)[:, 0]
    sel = jnp.where(k1[:, 1] < p_col, col, a_col)
    out = jnp.take_along_axis(nbr, sel[:, None], axis=1)[:, 0]
    return jnp.where(total > 0, out, -1)


# Baseline step functions by registry name (samplers.py wraps each in a
# PaddedRowSampler; benchmarks may call them directly on padded blocks).
BASELINE_STEP_FNS = {
    "its": its_step,
    "als": als_step,
    "rvs_prefix": rvs_prefix_step,
    "rjs_maxreduce": rjs_maxreduce_step,
}
