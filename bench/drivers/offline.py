"""Offline corpus generation through the engine's epoch scheduler.

The window drives ``WalkEngine.scheduler()`` — ``EpochScheduler.admit``
at every epoch boundary and ``run_epoch`` — the loop ``WalkEngine.run``
runs, fed from the cell's corpus (one ``run`` call over a corpus would
not return inside a window).  ``walk_steps_per_s`` is every live
walker-step of the window's epochs (the ``live`` counter) over the
window's whole time; the window is whole epochs and lasts at least
``--seconds``.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

import floor_bytes
import graphgen
import harness
import reference
import traffic


def run(ctx: harness.Context) -> harness.Run:
    import jax
    from repro.core import EngineConfig, WalkEngine

    cfg, mix = ctx.config, ctx.traffic
    spec = cfg["program"]
    L, T, W = int(spec["walk_len"]), int(cfg["engine"]["epoch_len"]), \
        int(cfg["slots"])
    n_dev = int(cfg["devices"])
    indptr, indices, h = graphgen.make_graph(cfg["graph"], ctx.seed)
    degrees = np.diff(indptr)
    eng = WalkEngine(harness.device_graph(indptr, indices, h),
                     harness.program(spec),
                     EngineConfig(method=cfg["engine"]["method"],
                                  epoch_len=T))
    sched = eng.scheduler(num_steps=L,
                          key=jax.random.key(ctx.sub_seed("walks")),
                          slots=W, epoch_len=T, capacity=4 * W,
                          devices=n_dev if n_dev > 1 else None)
    blocks = traffic.corpus_blocks(mix, degrees, ctx.seed)
    queue = deque()
    starts, admitted_at = [], []  # per query id
    epochs = 0

    def refill():
        free = sched.free_slots().size
        while len(queue) < free:
            queue.extend(next(blocks).tolist())
        if free:
            qs = np.arange(len(starts), len(starts) + free)
            st = np.asarray([queue.popleft() for _ in range(free)],
                            np.int32)
            sched.admit(qs, st)
            starts.extend(st.tolist())
            admitted_at.extend([epochs] * free)

    completed = []
    # warm-up: the first admission and one epoch compile every program
    # the window runs (a refill is always a whole pool: walks have one
    # length, so the pool empties at once)
    refill()
    rep = sched.run_epoch()
    epochs += 1
    completed.extend(rep.completed.tolist())
    jax.block_until_ready(sched.state.cur)
    setup_s = time.perf_counter() - ctx.t_start

    win = harness.Window(ctx.trace, ctx.compiles)
    totals = {k: 0 for k in sched.totals}
    window_epochs = 0
    hops_before = (sched.paths[:, 1:] >= 0).sum(axis=1)
    with win.measure():
        while True:
            with jax.profiler.TraceAnnotation("bench.admit"):
                refill()
            with jax.profiler.TraceAnnotation("bench.run_epoch"):
                rep = sched.run_epoch()
            epochs += 1
            completed.extend(rep.completed.tolist())
            for k in totals:
                totals[k] += rep.stats[k]
            window_epochs += 1
            if time.perf_counter() - win.t0 >= ctx.seconds:
                break
    devices = list(sched.mesh.devices.flat) if sched.mesh is not None \
        else [jax.devices()[0]]
    mem = harness.memory_peak(devices)
    platform = devices[0].platform
    reduced = win.reduce(kernels=cfg["kernels"], platform=platform)

    n = len(starts)
    paths = sched.paths[:n].copy()
    starts = np.asarray(starts, np.int32)
    complete = np.zeros(n, bool)
    complete[np.asarray(completed, np.int64)] = True
    expect = T * (epochs - np.asarray(admitted_at, np.int64))
    del sched, eng  # the program's state is freed before the reference

    g = reference.Graph(indptr, indices, h)
    # hops the window emitted (for the floor byte count)
    before = np.zeros(n, np.int64)
    m = min(n, hops_before.shape[0])
    before[:m] = hops_before[:m]
    q, k, prev, _, _ = reference.walk_hops(paths)
    in_window = k > before[q]
    prev_deg = np.where(prev[in_window] >= 0,
                        g.deg[np.maximum(prev[in_window], 0)], -1)

    lim = cfg["limits"]
    rng = traffic.rng_for(ctx.seed, "check")
    s_prev, s_cur, s_nxt = reference.sample_hops(paths, cfg["pit_sample"],
                                                 rng)
    u = reference.pit_values(spec, g, s_prev, s_cur, s_nxt, rng)
    checks = {
        "bad_hops": (reference.form_errors(g, starts, paths),
                     lim["bad_hops"]),
        "bad_lengths": (reference.length_errors(spec, g, paths, complete,
                                                expect),
                        lim["bad_lengths"]),
        "pit_ks": (reference.ks_sqrt_n(u), lim["pit_ks"]),
    }
    if ctx.control:
        x = reference.control_draws(spec, g, s_prev, s_cur, rng)
        checks["control.pit_ks"] = (reference.ks_sqrt_n(
            reference.pit_values(spec, g, s_prev, s_cur, x, rng)),
            lim["pit_ks"])
    live = totals["live"]
    record = {
        "trace": reduced,
        "scan_steps": window_epochs * T,
        "live": live,
        "rjs_served": totals["rjs_served"],
        "floor_bytes": floor_bytes.hop_floor_bytes(spec["kind"], prev_deg),
        "compiles_in_window": win.compiles_in_window,
        "memory_peak_bytes": mem,
        "peaks": ctx.peaks,
    }
    return harness.Run(
        setup_s=setup_s,
        e2e={"walk_steps_per_s": live / win.seconds},
        attempted=n, failed=0, checks=checks, record=record,
        memory_peak_bytes=mem, device_count=len(devices))
