"""Batched edge-context construction shared by all sampling kernels.

Builds EdgeCtx blocks of shape [W, T] (walkers × neighbor tile) from CSR,
computing only the fields the workload declared it needs (dist is a binary
search per edge, ``depth`` rounds deep; labels are a gather — both skipped
when unused).  Callers pass ``depth = search_depth(bound)`` for a static
bound on every row's length (``graphs/csr.py``), the engine's ``pad``.

Rows are read through the ``row_starts`` / ``row_degs`` accessor protocol
shared by ``CSRGraph`` and ``graphs.delta.OverlayGraph``, so every
sampler built on these helpers serves delta-overlay (structurally
mutated) graphs unchanged.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import EdgeCtx, Workload
from repro.graphs.csr import CSRGraph, dist_code


def degrees_of(graph: CSRGraph, v: jax.Array) -> jax.Array:
    vs = jnp.maximum(v, 0)
    d = graph.row_degs(vs)
    return jnp.where(v >= 0, d, 0).astype(jnp.int32)


def tile_ctx(
    graph: CSRGraph,
    workload: Workload,
    cur: jax.Array,  # [W]
    prev: jax.Array,  # [W]
    step: jax.Array,  # [W]
    tile_start: jax.Array,  # [] or [W] — offset within each row
    tile: int,
    *,
    depth: int,  # dist search rounds: search_depth of a row-length bound
) -> Tuple[EdgeCtx, jax.Array]:
    """Return (ctx[W, T], mask[W, T]) for neighbours [tile_start, tile_start+T)."""
    W = cur.shape[0]
    start = graph.row_starts(jnp.maximum(cur, 0))
    deg_cur = degrees_of(graph, cur)
    deg_prev = degrees_of(graph, prev)
    offs = tile_start[..., None] + jnp.arange(tile, dtype=jnp.int32)[None, :]
    mask = offs < deg_cur[:, None]
    pos = jnp.clip(start[:, None] + offs, 0, graph.num_edges - 1)
    nbr = jnp.where(mask, graph.indices[pos], -1)
    h = jnp.where(mask, graph.h[pos], 0.0) if workload.weighted else jnp.where(mask, 1.0, 0.0)
    if workload.needs_labels:
        label = jnp.where(mask, graph.labels[pos], -1)
    else:
        label = jnp.zeros_like(nbr)
    if workload.needs_dist:
        dist = jax.vmap(lambda p, us: jax.vmap(
            lambda u: _dist_code(graph, p, u, depth))(us))(prev, nbr)
    else:
        dist = jnp.ones_like(nbr)
    ctx = EdgeCtx(
        h=h,
        label=label,
        dist=dist,
        nbr=nbr,
        deg_cur=jnp.broadcast_to(deg_cur[:, None], (W, tile)),
        deg_prev=jnp.broadcast_to(deg_prev[:, None], (W, tile)),
        cur=jnp.broadcast_to(cur[:, None], (W, tile)),
        prev=jnp.broadcast_to(prev[:, None], (W, tile)),
        step=jnp.broadcast_to(step[:, None], (W, tile)),
    )
    return ctx, mask


def single_edge_ctx(
    graph: CSRGraph,
    workload: Workload,
    cur: jax.Array,  # [W]
    prev: jax.Array,  # [W]
    step: jax.Array,  # [W]
    offset: jax.Array,  # [W] — neighbour offset within the row (one trial)
    *,
    depth: int,  # as tile_ctx's
) -> Tuple[EdgeCtx, jax.Array]:
    """EdgeCtx for exactly one candidate edge per walker (rejection trials)."""
    deg_cur = degrees_of(graph, cur)
    deg_prev = degrees_of(graph, prev)
    valid = offset < deg_cur
    pos = jnp.clip(graph.row_starts(jnp.maximum(cur, 0)) + offset, 0,
                   graph.num_edges - 1)
    nbr = jnp.where(valid, graph.indices[pos], -1)
    h = jnp.where(valid, graph.h[pos], 0.0) if workload.weighted else jnp.where(valid, 1.0, 0.0)
    label = jnp.where(valid, graph.labels[pos], -1) if workload.needs_labels else jnp.zeros_like(nbr)
    if workload.needs_dist:
        dist = jax.vmap(
            lambda p, u: _dist_code(graph, p, u, depth))(prev, nbr)
    else:
        dist = jnp.ones_like(nbr)
    ctx = EdgeCtx(
        h=h, label=label, dist=dist, nbr=nbr,
        deg_cur=deg_cur, deg_prev=deg_prev, cur=cur, prev=prev, step=step,
    )
    return ctx, valid


def _dist_code(graph: CSRGraph, v_prev: jax.Array, u: jax.Array,
               depth: int) -> jax.Array:
    return dist_code(graph, v_prev, jnp.maximum(u, 0), depth)


def eval_weights(workload: Workload, params, ctx: EdgeCtx, mask: jax.Array,
                 wstate=None) -> jax.Array:
    """w̃ for a ctx block; masked lanes get 0 (never sampled).

    ``wstate`` is the per-walker program state (leaves lead with the
    walker dim, matching ``ctx``'s OUTERMOST dim); it is broadcast over
    the neighbour-tile dims — every candidate edge of a walker sees the
    same state.  ``None`` for stateless programs.
    """
    flat_fn = workload.edge_weight
    # inner dims (neighbour tiles): map ctx only, broadcast wstate
    for _ in range(max(ctx.h.ndim - 1, 0)):
        flat_fn = jax.vmap(flat_fn, in_axes=(0, None, None))
    # outermost dim (walkers): map ctx AND wstate together
    if ctx.h.ndim:
        flat_fn = jax.vmap(flat_fn, in_axes=(0, None, 0))
    w = flat_fn(ctx, params, wstate)
    return jnp.where(mask, jnp.maximum(w, 0.0), 0.0)
