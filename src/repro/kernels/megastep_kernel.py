"""Fused mega-step Pallas kernel — one kernel per scheduler epoch.

Each grid step takes a block of walker *lanes* (``precomp_kernel.
walker_grid``) and runs, lane after lane, the ENTIRE per-step chain for
``epoch_len`` consecutive walk steps without returning to XLA between
stages (ThunderRW's gather-move-update interleaving; C-SAW's
warp-per-walker structure, with warps → lanes of a block):

  neighbour-tile DMA from the tile-aligned CSR stream
    → WalkProgram weight evaluation (programs the Flexi-Compiler proves
      fusable: ``fc.fuse_report``)
    → per-lane regime pick (reservoir / rejection / precomp table draw)
    → ``on_step`` wstate commit + ``should_stop`` alive fold
    → StepStats flag accumulation.

Bit-identity contract (tests/test_megastep.py, tests/test_conformance.py)
-------------------------------------------------------------------------
The kernel calls the SAME counter-based Threefry functions as the staged
scan (``kernels/prng.py``: per-step key = ``threefry2x32(rng, 0, step)``
= ``WalkerState.stream_keys()``, ``tile_uniforms`` for eRVS keys,
``trial_uniform`` for eRJS trials, ``uniform_01``/``uniform_pair_01``
with the shared ITS/ALIAS salts for table draws), and applies the same
masks in the same order — so for every fusable (sampler × program) cell
``step_exec=fused`` produces byte-identical paths AND telemetry to
``step_exec=staged``.  That makes the staged scan a true fallback, not a
different estimator.

Per-step telemetry is accumulated as a per-(step, lane) int32 flag word
(bit positions = ``StepStats.LIVE`` …) and reduced to ``StepStats``
outside the kernel — integer sums, so the reduction is order-free exact.

Layout: edge streams are ``ops.align_rows`` [R, 128] tiles (every row
starts on a lane boundary; ≥2 slack sublane-rows so a trailing DMA never
reads out of bounds); per-node scalars ride ``pack_node_stream`` [V→pad,
128] streams so in-kernel degree/row0/bound/total lookups are one (8,
128) DMA each (``precomp_kernel.read_elem``).  Per-lane state (cur, prev,
step, alive, key words, scalar wstate leaves) rides (B,) SMEM windows;
the [T, W] emitted/flag outputs are (T, B) VMEM blocks written one step
row at a time.  ``default_interpret()`` gates compiled vs interpret mode
exactly like the precomp kernels.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import EdgeCtx, StepStats, WalkerState
from repro.graphs.delta import host_row_layout
from repro.kernels.ops import align_rows_layout
from repro.kernels.precomp_kernel import (default_interpret, dma_tiles,
                                          lane_pick, pad_lanes, read_elem,
                                          read_elems, smem_lanes,
                                          walker_grid)
from repro.kernels.prng import (ALIAS_SALT, ITS_SALT, threefry2x32,
                                threefry_seeds, tile_uniforms, trial_uniform,
                                uniform_01, uniform_pair_01)
from repro.kernels.ref import LANES, SUBLANES, TILE

#: regime kinds a sampler may declare fusable (``Sampler.fused_kind``)
FUSED_KINDS = ("reservoir", "rejection", "precomp_its", "precomp_alias")

# np scalar (not a jnp array: Pallas kernels may not capture device-array
# constants) — same float32 -inf bits as ervs.NEG_INF
_NEG_INF = np.float32(-np.inf)


def wstate_refusal(leaves, lane_shape=()) -> Optional[str]:
    """Why the kernel cannot hold these wstate leaves, or None.  It keeps
    each leaf as one 32-bit scalar per lane in a (B,) SMEM window, so
    every leaf must have shape ``lane_shape`` — ``()`` for the per-walker
    template (``fuse_report``), ``(W,)`` for a batched state."""
    for leaf in leaves:
        if leaf.shape != tuple(lane_shape) or leaf.dtype.itemsize > 4:
            return (f"wstate leaf {leaf.shape}/{leaf.dtype} is not a 32-bit "
                    f"scalar per lane — the kernel keeps per-lane state in "
                    f"scalar memory")
    return None


def _log_keys(u, w):
    """Bit-exact replica of ``ervs._log_keys`` (ln(u)/w̃, -inf for w̃≤0)."""
    safe_w = jnp.where(w > 0, w, 1.0)
    lk = jnp.log(u) / safe_w
    return jnp.where(w > 0, lk, _NEG_INF)


def pack_node_stream(x) -> jnp.ndarray:
    """Pack a per-node [V] vector into a DMA-able [pad/128, 128] stream.

    Padded to a whole number of (8, 128) tiles, so the element read at
    any v < V touches rows that exist — no slack needed (works for both
    host-side numpy constants and traced per-epoch jnp arrays)."""
    x = jnp.asarray(x)
    V = max(int(x.shape[0]), 1)
    pad = -(-V // TILE) * TILE
    flat = jnp.zeros((pad,), x.dtype).at[:x.shape[0]].set(x)
    return flat.reshape(pad // LANES, LANES)


def _span(r0, start, tile: int, *streams):
    """The ``tile`` elements from offset ``start`` (a multiple of
    ``tile``, which divides TILE — the span never crosses a DMA tile) of
    the row at sublane-row ``r0`` in each ``(hbm, buf, sem)`` stream.
    Returns ``(j, *vals)``, all (max(tile/128, 1), 128): ``j`` is each
    lane's offset within the span, in [0, tile) on the span's lanes and
    outside it on the rest of a shared row."""
    blk = start // TILE
    dma_tiles(r0 + blk * SUBLANES, *streams)
    within = start - blk * TILE
    nr = max(tile // LANES, 1)
    j = (jax.lax.broadcasted_iota(jnp.int32, (nr, LANES), 0) * LANES
         + jax.lax.broadcasted_iota(jnp.int32, (nr, LANES), 1)
         - within % LANES)
    return (j,) + tuple(buf[pl.ds(within // LANES, nr), :]
                        for _, buf, _ in streams)


# ------------------------------------------------------------------ kernel
def _make_kernel(program, params, *, kind: str, tile: int, max_tiles: int,
                 rjs_trials: int, rjs_max_rounds: int, epoch_len: int,
                 num_steps: int, n_streams: int, ws_dtypes, ws_treedef):
    """Build the mega-step kernel body (refs sliced positionally)."""
    K, R = rjs_trials, rjs_max_rounds
    LIVE, RJS = StepStats.LIVE, StepStats.RJS
    FALLBACK, PRECOMP, STALE = (StepStats.FALLBACK, StepStats.PRECOMP,
                                StepStats.STALE)
    n_ws = len(ws_dtypes)
    # per-candidate weights: the staged eval_weights' map over edges with
    # the walker's params/wstate broadcast, once per (row, lane) axis
    tile_weight = jax.vmap(jax.vmap(program.edge_weight,
                                    in_axes=(0, None, None)),
                           in_axes=(0, None, None))

    def kernel(*refs):
        cur_s, prev_s, step_s, alive_s, k0_s, k1_s = refs[:6]
        streams = refs[6:6 + n_streams]
        ws_refs = refs[6 + n_streams:6 + n_streams + n_ws]
        k = 6 + n_streams + n_ws
        em_ref, fl_ref, ocur, oprev, ostep, oalive = refs[k:k + 6]
        ws_out = refs[k + 6:k + 6 + n_ws]
        ibuf, fbuf, isem, fsem = refs[k + 6 + n_ws:]
        deg_nd, row0_nd, nbr_hbm, h_hbm = streams[:4]
        B = em_ref.shape[1]
        lane_of_block = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

        def node_read_i32(nd, v):
            return read_elem(nd, ibuf, isem, jnp.int32(0), v)

        def node_read_f32(nd, v):
            return read_elem(nd, fbuf, fsem, jnp.int32(0), v)

        def deg_of(v):
            # degrees_of() semantics: 0 for the -1 sentinel
            d = node_read_i32(deg_nd, jnp.maximum(v, 0))
            return jnp.where(v >= 0, d, 0).astype(jnp.int32)

        # ---------------------------------------------- per-lane regimes
        def reservoir_lane(cur, deg, sk0, sk1, prev, stepc, ws_tree, act):
            """ervs_step for one lane; per-lane trip count ≡ the staged
            cross-lane max (masked tiles are all-NEG_INF no-ops under the
            strict > update)."""
            r0row = node_read_i32(row0_nd, jnp.maximum(cur, 0))
            dprev = deg_of(prev)
            ntiles = jnp.where(
                act, jnp.minimum((deg + tile - 1) // tile, max_tiles), 0)

            def body(t, carry):
                best_lk, best_nbr = carry
                tstart = t * tile
                j, nbr_raw, h_raw = _span(r0row, tstart, tile,
                                          (nbr_hbm, ibuf, isem),
                                          (h_hbm, fbuf, fsem))
                inwin = (j >= 0) & (j < tile)
                mask = inwin & (tstart + j < deg)
                nbr = jnp.where(mask, nbr_raw, -1)
                h = jnp.where(mask, h_raw, jnp.float32(0.0))

                def bc(x):
                    return jnp.broadcast_to(x, j.shape)

                ctx = EdgeCtx(
                    h=h, label=jnp.zeros_like(nbr), dist=jnp.ones_like(nbr),
                    nbr=nbr, deg_cur=bc(deg), deg_prev=bc(dprev),
                    cur=bc(cur), prev=bc(prev), step=bc(stepc))
                w_raw = tile_weight(ctx, params, ws_tree)
                w = jnp.where(mask, jnp.maximum(w_raw, 0.0), 0.0)
                u = tile_uniforms(sk0, sk1, t, j)
                lk = jnp.where(mask, _log_keys(u, w), _NEG_INF)
                # first arg-max within the span (jnp.argmax's tie rule)
                m = jnp.max(lk)
                b = jnp.min(jnp.where((lk == m) & inwin, j, tile))
                nb = lane_pick(nbr, b, where=jnp.where(inwin, j, -1))
                upd = m > best_lk
                return (jnp.where(upd, m, best_lk),
                        jnp.where(upd, nb, best_nbr))

            _, best_nbr = jax.lax.fori_loop(
                0, ntiles, body, (_NEG_INF, jnp.int32(-1)))
            return best_nbr

        def rejection_lane(cur, deg, sk0, sk1, prev, stepc, ws_tree, act):
            """erjs_step + reservoir fallback for one lane (the staged
            round×trial grid flattened: trial t ↔ (r, k) = divmod(t, K),
            counters 2t/2t+1 ≡ r·2K+2k / +1)."""
            bound = node_read_f32(streams[4], jnp.maximum(cur, 0))
            r0row = node_read_i32(row0_nd, jnp.maximum(cur, 0))
            dprev = deg_of(prev)
            feasible = act & (deg > 0) & (bound > 0)

            def cond(c):
                t, done, _ = c
                return (t < K * R) & ~done

            def body(c):
                t, done, chosen = c
                u_idx = trial_uniform(sk0, sk1, 2 * t)
                u_acc = trial_uniform(sk0, sk1, 2 * t + 1)
                offset = jnp.minimum(
                    (u_idx * deg.astype(jnp.float32)).astype(jnp.int32),
                    jnp.maximum(deg - 1, 0))
                nbr_c, h_c = read_elems(r0row, offset, (nbr_hbm, ibuf, isem),
                                        (h_hbm, fbuf, fsem))
                ctx = EdgeCtx(
                    h=h_c, label=jnp.zeros_like(nbr_c),
                    dist=jnp.ones_like(nbr_c), nbr=nbr_c, deg_cur=deg,
                    deg_prev=dprev, cur=cur, prev=prev, step=stepc)
                w = jnp.maximum(program.edge_weight(ctx, params, ws_tree),
                                0.0)
                accept = feasible & ~done & (u_acc * bound <= w) & (w > 0)
                return (t + 1, done | accept,
                        jnp.where(accept, nbr_c, chosen))

            _, done, chosen = jax.lax.while_loop(
                cond, body, (jnp.int32(0), ~feasible, jnp.int32(-1)))
            fb = feasible & ~done
            res = reservoir_lane(cur, deg, sk0, sk1, prev, stepc, ws_tree, fb)
            nxt = jnp.where(fb, res, chosen)
            extra = (jnp.where(~fb & (chosen >= 0), 1 << RJS, 0)
                     | jnp.where(fb, 1 << FALLBACK, 0))
            return nxt, extra.astype(jnp.int32)

        def precomp_lane(cur, deg, sk0, sk1, prev, stepc, ws_tree, act):
            """_PrecompBase.select for one lane: table draw on valid rows,
            reservoir on stale ones."""
            if kind == "precomp_its":
                cdf_hbm, total_nd, inval_nd = streams[4:7]
            else:
                prob_hbm, alias_hbm, total_nd, inval_nd = streams[4:8]
            vpos = jnp.maximum(cur, 0)
            ok = act & (cur >= 0) & (node_read_i32(inval_nd, vpos) == 0)
            total = node_read_f32(total_nd, vpos)
            r0row = node_read_i32(row0_nd, vpos)
            if kind == "precomp_its":
                u = uniform_01(sk0, sk1, jnp.uint32(0), jnp.uint32(ITS_SALT))
                target = u * total

                def scond(c):
                    lo, hi = c
                    return lo < hi

                def sbody(c):
                    lo, hi = c
                    mid = (lo + hi) // 2
                    go = read_elem(cdf_hbm, fbuf, fsem, r0row, mid) <= target
                    return (jnp.where(go, mid + 1, lo),
                            jnp.where(go, hi, mid))

                lo, _ = jax.lax.while_loop(
                    scond, sbody,
                    (jnp.int32(0), jnp.where(ok, deg, 0)))
                sel = jnp.clip(lo, 0, jnp.maximum(deg - 1, 0))
            else:
                u1, u2 = uniform_pair_01(sk0, sk1, jnp.uint32(0),
                                         jnp.uint32(ALIAS_SALT))
                col = jnp.minimum(
                    (u1 * deg.astype(jnp.float32)).astype(jnp.int32),
                    jnp.maximum(deg - 1, 0))
                p_c = read_elem(prob_hbm, fbuf, fsem, r0row, col)
                a_c = read_elem(alias_hbm, fbuf, fsem, r0row,
                                col).astype(jnp.int32)
                sel = jnp.where(u2 < p_c, col, a_c)
            nbr_c = read_elem(nbr_hbm, ibuf, isem, r0row, sel)
            nxt_pre = jnp.where(ok & (deg > 0) & (total > 0), nbr_c, -1)
            stale = act & ~ok
            dyn = reservoir_lane(cur, deg, sk0, sk1, prev, stepc, ws_tree,
                                 stale)
            nxt = jnp.where(ok, nxt_pre, jnp.where(stale, dyn, -1))
            extra = (jnp.where(ok & (nxt_pre >= 0), 1 << PRECOMP, 0)
                     | jnp.where(stale & (dyn >= 0), 1 << STALE, 0))
            return nxt, extra.astype(jnp.int32)

        # ------------------------------------------------- epoch step loop
        def lane_body(b, carry):
            s0 = k0_s[b]
            s1 = k1_s[b]
            here = lane_of_block == b

            def put(ref, t, val):
                # scalar → (t, b) of a (T, B) VMEM block: one row RMW
                row = ref[pl.ds(t, 1), :]
                ref[pl.ds(t, 1), :] = jnp.where(here, val, row)

            def step_body(t, c):
                cur, prev, stepc, alive, ws_leaves = c
                ws_tree = jax.tree_util.tree_unflatten(ws_treedef,
                                                       list(ws_leaves))
                deg = deg_of(cur)
                wants = alive & (stepc < num_steps)
                live = wants & (deg > 0)
                # per-step key: stream_keys() folds the step counter
                sk0, sk1 = threefry2x32(s0, s1, jnp.uint32(0), stepc)
                if kind == "reservoir":
                    nxt = reservoir_lane(cur, deg, sk0, sk1, prev, stepc,
                                         ws_tree, live)
                    extra = jnp.int32(0)
                elif kind == "rejection":
                    nxt, extra = rejection_lane(cur, deg, sk0, sk1, prev,
                                                stepc, ws_tree, live)
                else:
                    nxt, extra = precomp_lane(cur, deg, sk0, sk1, prev,
                                              stepc, ws_tree, live)
                nxt = jnp.where(live, nxt, -1)
                stepped = live & (nxt >= 0)
                flagw = jnp.where(live, jnp.int32(1 << LIVE) | extra,
                                  jnp.int32(0))
                # --- WalkProgram hooks, exactly as the staged step orders
                new_leaves = ws_leaves
                stop = jnp.zeros_like(stepped)
                if program.has_hooks:
                    tctx = EdgeCtx(
                        h=jnp.float32(1.0), label=jnp.int32(-1),
                        dist=jnp.int32(-1), nbr=nxt, deg_cur=deg,
                        deg_prev=deg_of(prev), cur=cur, prev=prev,
                        step=stepc)
                    new_ws = ws_tree
                    if program.on_step is not None:
                        cand = program.on_step(tctx, params, ws_tree)
                        new_leaves = tuple(
                            jnp.where(stepped, n, o) for n, o in
                            zip(jax.tree_util.tree_leaves(cand), ws_leaves))
                        new_ws = jax.tree_util.tree_unflatten(
                            ws_treedef, list(new_leaves))
                    if program.should_stop is not None:
                        stop = stepped & program.should_stop(tctx, params,
                                                             new_ws)
                put(em_ref, t, jnp.where(stepped, nxt, -1))
                put(fl_ref, t, flagw)
                return (jnp.where(stepped, nxt, cur),
                        jnp.where(stepped, cur, prev),
                        stepc + stepped.astype(jnp.int32),
                        alive & ~(wants & ~stepped) & ~stop,
                        new_leaves)

            init = (cur_s[b], prev_s[b], step_s[b], alive_s[b] != 0,
                    tuple(r[b] != 0 if dt == jnp.bool_ else r[b]
                          for r, dt in zip(ws_refs, ws_dtypes)))
            cur, prev, stepc, alive, ws_leaves = jax.lax.fori_loop(
                0, epoch_len, step_body, init)
            ocur[b] = cur
            oprev[b] = prev
            ostep[b] = stepc
            oalive[b] = alive.astype(jnp.int32)
            for r, v in zip(ws_out, ws_leaves):
                r[b] = v.astype(r.dtype)
            return carry

        jax.lax.fori_loop(0, B, lane_body, 0)

    return kernel


# ----------------------------------------------------------------- wrapper
def fused_streams(graph, program, *, bmax=None, bucket_rows: bool = False):
    """Host-side tile-aligned edge streams for the mega-step kernel:
    ``(deg_nd, row0_nd, nbr2d, h2d[, bmax_nd])``.

    Works on a contiguous ``CSRGraph`` AND a delta-overlay
    ``OverlayGraph`` — the kernel body is layout-agnostic (it reads
    per-node ``deg``/``row0`` streams and never assumes contiguity), so
    aligning the overlay's ``row_start``/``row_deg`` layout produces
    exactly the streams a compacted graph would: dead patch space is
    never gathered, and the within-row order (the RNG key) is identical.

    ``bucket_rows=True`` pow2-pads the aligned row count so a mutation
    burst produces O(log K) distinct stream shapes (→ O(log K) retraces
    of the jitted fused epoch, matching the staged path's shape
    bucketing).  Pass ``bmax`` (per-node weight bound table) for the
    rejection regime.
    """
    starts, degs_h = host_row_layout(graph)
    indices = np.asarray(graph.indices)
    nbr2d, row0, degs = align_rows_layout(indices, starts, degs_h,
                                          dtype=np.int32,
                                          bucket_rows=bucket_rows)
    if program.weighted:
        h_vals = np.asarray(graph.h)
    else:  # unweighted programs see ctx.h == 1 on every real edge
        h_vals = np.ones(int(indices.shape[0]), np.float32)
    h2d, _, _ = align_rows_layout(h_vals, starts, degs_h,
                                  bucket_rows=bucket_rows)
    streams = [pack_node_stream(degs), pack_node_stream(row0), nbr2d, h2d]
    if bmax is not None:
        streams.append(pack_node_stream(jnp.asarray(bmax, jnp.float32)))
    return tuple(streams)


def make_streamed_epoch(program, params, *, kind: str, tile: int,
                        rjs_trials: int = 8, rjs_max_rounds: int = 16,
                        interpret: Optional[bool] = None):
    """Build ``epoch(state, precomp, streams, epoch_len, num_steps,
    max_tiles)`` running the fused mega-step kernel.

    The edge streams (:func:`fused_streams`) are an *argument*, not a
    closure: the engine rebuilds them host-side after a structural
    mutation and the jitted epoch retraces only when their shapes change
    (pow2-bucketed → O(log K) variants per burst), exactly like the
    staged epoch treats the graph.  ``max_tiles`` rides along the same
    way (a static arg at the jit boundary) so pad-bucket growth retraces
    instead of requiring a rebuild.  Precomp kinds read the aligned
    table streams off the ``precomp`` argument at call time, so
    between-epoch rebuild drains swap in re-baked rows with no retrace.
    """
    if kind not in FUSED_KINDS:
        raise ValueError(f"kind {kind!r} not one of {FUSED_KINDS}")
    if tile < 2 or tile % 2 or TILE % tile:
        raise ValueError(
            f"fused step needs an even tile dividing {TILE}, got {tile}")
    interpret = default_interpret() if interpret is None else bool(interpret)

    def epoch(state: WalkerState, precomp, in_streams, epoch_len: int,
              num_steps: int, max_tiles: int):
        want = 5 if kind == "rejection" else 4
        if len(in_streams) != want:
            raise ValueError(
                f"kind={kind!r} expects {want} edge streams "
                f"(fused_streams{' with bmax' if want == 5 else ''}), "
                f"got {len(in_streams)}")
        W = int(state.cur.shape[0])
        seeds = threefry_seeds(state.rng)
        streams = list(in_streams)
        if kind in ("precomp_its", "precomp_alias"):
            if precomp is None or precomp.cdf2d is None:
                raise ValueError(
                    f"kind={kind!r} needs aligned precomp tables "
                    f"(build_tables(..., aligned=True))")
            if kind == "precomp_its":
                streams.append(precomp.cdf2d)
            else:
                streams.extend([precomp.prob2d, precomp.alias2d])
            streams.append(pack_node_stream(
                jnp.asarray(precomp.total, jnp.float32)))
            streams.append(pack_node_stream(
                jnp.asarray(precomp.invalid, jnp.int32)))
        ws_leaves, ws_treedef = jax.tree_util.tree_flatten(state.wstate)
        why = wstate_refusal(ws_leaves, (W,))
        if why is not None:  # fuse_report refuses such programs first
            raise ValueError(why)
        ws_dtypes = tuple(l.dtype for l in ws_leaves)
        B, Wp = walker_grid(W)
        lanes = [pad_lanes(x, Wp) for x in (
            state.cur.astype(jnp.int32), state.prev.astype(jnp.int32),
            state.step.astype(jnp.int32), state.alive.astype(jnp.int32),
            seeds[:, 0], seeds[:, 1])]
        ws_in = [pad_lanes(l.astype(jnp.int32) if l.dtype == bool else l, Wp)
                 for l in ws_leaves]
        kernel = _make_kernel(
            program, params, kind=kind, tile=tile, max_tiles=int(max_tiles),
            rjs_trials=rjs_trials, rjs_max_rounds=rjs_max_rounds,
            epoch_len=int(epoch_len), num_steps=int(num_steps),
            n_streams=len(streams), ws_dtypes=ws_dtypes,
            ws_treedef=ws_treedef)
        T = int(epoch_len)
        step_rows = pl.BlockSpec((T, B), lambda i: (0, i))
        outs = pl.pallas_call(
            kernel, grid=(Wp // B,),
            in_specs=([smem_lanes(B)] * 6
                      + [pl.BlockSpec(memory_space=pl.ANY)] * len(streams)
                      + [smem_lanes(B)] * len(ws_in)),
            out_specs=[step_rows] * 2 + [smem_lanes(B)] * (4 + len(ws_in)),
            out_shape=([jax.ShapeDtypeStruct((T, Wp), jnp.int32)] * 2
                       + [jax.ShapeDtypeStruct((Wp,), jnp.int32)] * 4
                       + [jax.ShapeDtypeStruct((Wp,), l.dtype)
                          for l in ws_in]),
            scratch_shapes=[
                pltpu.VMEM((SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((SUBLANES, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
            interpret=interpret,
            name=f"megastep_{kind}",
        )(*lanes, *streams, *ws_in)
        emitted, flags = outs[0][:, :W], outs[1][:, :W]
        cur, prev, stepc, alive = (o[:W] for o in outs[2:6])
        ws_new = [o[:W] != 0 if dt == jnp.bool_ else o[:W]
                  for o, dt in zip(outs[6:], ws_dtypes)]
        new_state = WalkerState(
            cur=cur, prev=prev, step=stepc, alive=alive.astype(bool),
            rng=state.rng, carry=state.carry,
            wstate=jax.tree_util.tree_unflatten(ws_treedef, ws_new))
        return new_state, emitted, StepStats.from_flag_bits(flags.T)

    return epoch


def make_fused_epoch(graph, program, params, *, kind: str, tile: int,
                     max_tiles: int, rjs_trials: int = 8,
                     rjs_max_rounds: int = 16, bmax=None,
                     interpret: Optional[bool] = None):
    """Build ``epoch(state, precomp, epoch_len, num_steps)`` with the edge
    streams baked from ``graph`` at build time — the fixed-graph
    convenience over :func:`make_streamed_epoch` (same kernel, same
    bit-identity contract).  ``graph`` may be a contiguous ``CSRGraph``
    or a delta-overlay ``OverlayGraph`` (see :func:`fused_streams`)."""
    if kind == "rejection" and bmax is None:
        raise ValueError("kind='rejection' requires the baked bmax table")
    streams = fused_streams(graph, program,
                            bmax=bmax if kind == "rejection" else None)
    inner = make_streamed_epoch(program, params, kind=kind, tile=tile,
                                rjs_trials=rjs_trials,
                                rjs_max_rounds=rjs_max_rounds,
                                interpret=interpret)

    def epoch(state: WalkerState, precomp, epoch_len: int, num_steps: int):
        return inner(state, precomp, streams, epoch_len, num_steps,
                     max_tiles)

    return epoch
