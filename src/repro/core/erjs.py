"""eRJS — enhanced rejection sampling (paper §3.3).

The baseline RJS (NextDoor) pays a full pass over the row to find
max(w̃) before sampling.  eRJS replaces it with an *upper bound* c ≥ max(w̃)
computed from workload structure (Flexi-Compiler's get_weight_max), which
Eqs. 5–8 prove leaves the accepted distribution exactly p — only the
acceptance *rate* (1/c-ish) degrades if the bound is loose.

TPU adaptation: per-walker retry loops are vectorised across the batch —
each round draws K candidate offsets per walker, evaluates w̃ on those K
edges only (K gathers, not a row scan), accepts the first passing trial,
and a while_loop re-runs while any walker is unresolved, up to R_max
rounds.  Unresolved walkers are flagged for the reservoir-side fallback
(the paper's §7.1 safe mode doubles as straggler mitigation here: no
data-dependent loop runs past R_max).

Engine integration: ``samplers.ERJSRejection`` wraps this function as the
rejection half of any ``PartitionedSampler`` pair — the fallback mask it
returns is what moves unresolved lanes into the reservoir partition.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ctxutil import degrees_of, single_edge_ctx
from repro.core.types import Workload
from repro.graphs.csr import INT32_DEPTH, CSRGraph
from repro.kernels.prng import threefry_seeds, trial_uniform


@partial(jax.jit, static_argnames=("workload", "params", "trials_per_round",
                                   "max_rounds", "depth"))
def erjs_step(
    graph: CSRGraph,
    workload: Workload,
    params,
    cur: jax.Array,
    prev: jax.Array,
    step: jax.Array,
    rng: jax.Array,  # [W, 2]
    bound: jax.Array,  # [W] — c ≥ max_i w̃_i (from Flexi-Compiler or max-reduce)
    trials_per_round: int = 8,
    max_rounds: int = 16,
    active: Optional[jax.Array] = None,
    wstate=None,
    depth: int = INT32_DEPTH,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (next [W], needs_fallback [W] bool, rounds_used [] int32).

    ``depth`` is the ``dist`` search's (``graphs/csr.py`` ``search_depth``
    of a bound on every row's length); the default covers any int32 row.

    next = -2 for inactive walkers, -1 for zero-degree rows.
    needs_fallback marks walkers unresolved after max_rounds (engine runs
    eRVS for them — statistically fine: the accepted-so-far distribution is
    p regardless of when we stop proposing).
    """
    W = cur.shape[0]
    K = trials_per_round
    if active is None:
        active = jnp.ones((W,), bool)
    deg = degrees_of(graph, cur)
    feasible = active & (deg > 0) & (bound > 0)

    def round_body(state):
        r, done, chosen, _ = state

        def one_trial(k, inner):
            done_i, chosen_i = inner
            u_idx = _fold_uniform(rng, r * (2 * K) + 2 * k, W)
            u_acc = _fold_uniform(rng, r * (2 * K) + 2 * k + 1, W)
            # propose X ~ Uniform(N(v)) — the uniform proposal q of Eq. 5
            offset = jnp.minimum((u_idx * deg.astype(jnp.float32)).astype(jnp.int32),
                                 jnp.maximum(deg - 1, 0))
            ctx, valid = single_edge_ctx(graph, workload, cur, prev, step,
                                         offset, depth=depth)
            flat = jax.vmap(workload.edge_weight,
                            in_axes=(0, None, 0))(ctx, params, wstate)
            w = jnp.where(valid, jnp.maximum(flat, 0.0), 0.0)
            # accept iff u ≤ w̃(X)/c   (Eq. 5's U ≤ p(X)/(c·q(X)) with the
            # degree factors cancelled — c here bounds the raw weight)
            accept = feasible & (~done_i) & (u_acc * bound <= w) & (w > 0)
            chosen_i = jnp.where(accept, ctx.nbr, chosen_i)
            return (done_i | accept, chosen_i)

        done, chosen = jax.lax.fori_loop(0, K, one_trial, (done, chosen))
        return (r + 1, done, chosen, jnp.any(feasible & ~done))

    def cond(state):
        r, _, _, unresolved = state
        return jnp.logical_and(r < max_rounds, unresolved)

    r0 = jnp.int32(0)
    done0 = ~feasible  # infeasible walkers are trivially "done"
    chosen0 = jnp.full((W,), -1, jnp.int32)
    r, done, chosen, _ = jax.lax.while_loop(
        cond, round_body, (r0, done0, chosen0, jnp.any(feasible))
    )
    needs_fallback = feasible & ~done
    nxt = jnp.where(active, chosen, -2)
    return nxt, needs_fallback, r


def _fold_uniform(rng: jax.Array, counter, W: int) -> jax.Array:
    """Uniform number ``counter`` of each walker's step stream — the
    ``prng.trial_uniform`` the fused kernel draws per lane."""
    seeds = threefry_seeds(rng)
    return trial_uniform(seeds[:, 0], seeds[:, 1], counter)
