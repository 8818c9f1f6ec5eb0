"""Walk-service launcher — drive the continuously-batched serving loop.

    PYTHONPATH=src python -m repro.launch.serve_walks --trace overload \
        --queries 256 --slots 32 --max-pending 64 --sim-clock

Replays a scripted arrival trace (steady / burst / overload /
deadline-storm) against a live :class:`repro.serving.WalkService` and
reports the SLO telemetry: queries/s, p50/p99 queue wait and completion
latency, slot occupancy, and the rejected/expired counters.  With
``--sim-clock`` the whole trace runs on a deterministic simulated clock
(no sleeping, bit-identical replays — the mode the service test harness
pins); without it, arrivals pace against the wall clock.

``--transport tcp`` serves real clients instead of a scripted trace: a
:class:`repro.serving.WalkFrontend` listens on ``--host``/``--port``
(port 0 picks one; the bound port is printed on startup), clients speak
the length-prefixed JSON frame protocol (``repro.launch.walk_client``
is the stock client), and the server runs until a client sends a
``drain`` frame and every delivered walk has been polled out:

    PYTHONPATH=src python -m repro.launch.serve_walks \
        --transport tcp --port 7421 --slots 64

``--mutate-at T`` mutates the graph mid-serve, exercising the
rebuild-queue drain under live traffic: ``--mutate-kind weights``
(default) rescales edge weights through ``WalkService.update_graph``;
``--mutate-kind structural`` deletes and inserts edges through
``WalkService.apply_updates`` (the delta-overlay path — walks in
flight keep stepping over the mutated topology).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import EngineConfig
from repro.core.runtime import STEP_EXEC_CHOICES
from repro.core.samplers import PRECOMP_EXEC_CHOICES
from repro.graphs import power_law_graph, random_graph
from repro.serving import (FrontendConfig, ServiceConfig, SimClock,
                           WalkFrontend, WalkQuery, WalkService)
from repro.serving.frontend import SLOW_CLIENT_POLICIES
from repro.serving.walk_service import FAIRNESS_MODES
from repro.walks import WORKLOADS

TRACES = ("steady", "burst", "overload", "deadline-storm")


def parse_tenant_weights(spec: str) -> dict:
    """``"deepwalk=3,node2vec=1"`` -> ``{"deepwalk": 3.0, ...}``."""
    weights = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, value = part.partition("=")
        if not name or not value:
            raise ValueError(
                f"--tenant-weights entries must be name=weight, "
                f"got {part!r}")
        weights[name] = float(value)
    return weights


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface, as one inspectable object.

    ``tools/check_docs.py`` cross-checks every ``--flag`` the docs show
    in a ``repro.launch.serve_walks`` command against this parser, so a
    removed or renamed flag fails the docs gate instead of rotting.
    """
    ap = argparse.ArgumentParser(prog="repro.launch.serve_walks")
    # --- trace shape
    ap.add_argument("--trace", choices=TRACES, default="steady",
                    help="scripted arrival pattern: evenly spaced, a few "
                         "synchronized bursts, everything at t=0 against "
                         "a small pending bound (forcing queue-full "
                         "rejections), or tight per-query deadlines "
                         "(forcing infeasible rejections and expiries)")
    ap.add_argument("--queries", type=int, default=256,
                    help="total queries in the trace")
    ap.add_argument("--interarrival", type=float, default=0.01,
                    help="seconds between arrivals (steady) or bursts")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-query deadline budget in seconds after "
                         "arrival (default: only the deadline-storm "
                         "trace sets one)")
    ap.add_argument("--programs", default="deepwalk",
                    help="comma-separated walk programs to round-robin "
                         "queries over (multi-tenant serving), e.g. "
                         "deepwalk,node2vec")
    ap.add_argument("--mutate-at", type=float, default=None,
                    help="service-clock time at which to mutate the "
                         "graph mid-serve (see --mutate-kind)")
    ap.add_argument("--mutate-kind", choices=["weights", "structural"],
                    default="weights",
                    help="what --mutate-at mutates: 'weights' rescales "
                         "edge weights via update_graph; 'structural' "
                         "deletes and inserts edges via apply_updates "
                         "(the delta-overlay path)")
    # --- transport
    ap.add_argument("--transport", choices=["trace", "tcp"],
                    default="trace",
                    help="'trace' replays the scripted arrival trace "
                         "in-process; 'tcp' serves real clients over "
                         "the length-prefixed JSON frame protocol "
                         "(repro.launch.walk_client) until a client "
                         "drains the server")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --transport tcp")
    ap.add_argument("--port", type=int, default=0,
                    help="bind port for --transport tcp (0 picks an "
                         "ephemeral port; it is printed on startup)")
    ap.add_argument("--client-buffer", type=int, default=64,
                    help="per-connection delivery credits (buffered + "
                         "outstanding walks) before backpressure")
    ap.add_argument("--slow-client", choices=list(SLOW_CLIENT_POLICIES),
                    default="suspend",
                    help="over-credit submits are parked until a poll "
                         "frees credit ('suspend') or answered with a "
                         "typed backpressure error ('reject')")
    # --- fairness
    ap.add_argument("--fairness", choices=list(FAIRNESS_MODES),
                    default="drr",
                    help="cross-tenant scheduling: deficit round robin "
                         "in walker-steps ('drr') or the legacy one-"
                         "epoch-per-busy-tenant round robin ('epoch')")
    ap.add_argument("--quantum", type=int, default=None,
                    help="DRR walker-step credit per tenant per service "
                         "step (default: slots * epoch_len)")
    ap.add_argument("--tenant-weights", default="",
                    help="per-tenant DRR weights as name=w pairs, e.g. "
                         "deepwalk=3,node2vec=1 (unlisted tenants "
                         "weigh 1)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard every tenant's slot pool over this many "
                         "local devices (bit-identical to 1)")
    # --- clock
    ap.add_argument("--sim-clock", action="store_true",
                    help="run the trace on a deterministic simulated "
                         "clock (exact replays, no sleeping)")
    ap.add_argument("--tick", type=float, default=0.005,
                    help="simulated seconds advanced per service step "
                         "(sim-clock mode only)")
    # --- service knobs
    ap.add_argument("--slots", type=int, default=32,
                    help="walker slots per tenant program")
    ap.add_argument("--epoch-len", type=int, default=8,
                    help="scan steps between epoch boundaries (admission "
                         "/ expiry / streaming cadence)")
    ap.add_argument("--steps", type=int, default=None,
                    help="walk length served per query (default: each "
                         "program's walk_len)")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="pending-queue bound before queue-full rejection")
    ap.add_argument("--aging-interval", type=float, default=0.0,
                    help="seconds of queue wait per +1 effective "
                         "priority (0 disables aging)")
    # --- engine knobs (same semantics as repro.launch.walk)
    ap.add_argument("--method", default="adaptive")
    ap.add_argument("--precomp-exec", choices=list(PRECOMP_EXEC_CHOICES),
                    default="auto")
    ap.add_argument("--step-exec", choices=list(STEP_EXEC_CHOICES),
                    default="auto")
    ap.add_argument("--rebuild-budget", type=int, default=8)
    # --- graph
    ap.add_argument("--nodes", type=int, default=2_000)
    ap.add_argument("--avg-degree", type=int, default=12)
    ap.add_argument("--graph", choices=["random", "powerlaw"],
                    default="powerlaw")
    ap.add_argument("--weights", choices=["uniform", "pareto", "degree",
                                          "ones"], default="uniform")
    ap.add_argument("--alpha", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def scripted_trace(args, num_nodes: int) -> list:
    """The arrival script: a list of ``(arrival_time, WalkQuery)`` sorted
    by time — a pure function of the flags and seed, so a sim-clock run
    replays it exactly."""
    rng = np.random.default_rng(args.seed)
    programs = [p for p in args.programs.split(",") if p]
    starts = rng.integers(0, num_nodes, size=args.queries)
    priorities = rng.integers(0, 3, size=args.queries)
    if args.trace == "steady":
        times = np.arange(args.queries) * args.interarrival
    elif args.trace == "burst":
        # 4 synchronized bursts of queries/4 each
        times = (np.arange(args.queries) // max(args.queries // 4, 1)
                 ) * args.interarrival
    else:  # overload / deadline-storm: everything lands at t=0
        times = np.zeros(args.queries)
    deadline_budget = args.deadline
    if args.trace == "deadline-storm" and deadline_budget is None:
        deadline_budget = 0.05
    trace = []
    for i in range(args.queries):
        t = float(times[i])
        deadline = None
        if deadline_budget is not None:
            # storm: half the deadlines are generous, half are tight
            # enough that late-queued queries expire or get rejected
            scale = 1.0 if i % 2 == 0 else 0.1
            deadline = t + deadline_budget * scale
        trace.append((t, WalkQuery(
            start=int(starts[i]), program=programs[i % len(programs)],
            priority=int(priorities[i]), deadline=deadline)))
    return trace


def run_trace(svc: WalkService, trace: list, args,
              clock) -> tuple:
    """Drive the service through the trace until idle.  Returns
    ``(receipts, served)``.  Never deadlocks: every admitted walker
    terminates within ceil(steps/epoch_len) epochs, expiries free slots,
    and the loop always either submits, steps, or advances time."""
    mutated = args.mutate_at is None
    receipts, served, i = [], [], 0
    while i < len(trace) or not svc.idle:
        now = clock()
        if not mutated and now >= args.mutate_at:
            if args.mutate_kind == "structural":
                # deterministic seeded burst: delete a few existing
                # edges, insert a few random ones (an insert hitting a
                # surviving edge re-weights it — also exercised)
                rng = np.random.default_rng(args.seed + 1)
                indptr = np.asarray(svc.graph.indptr, np.int64)
                indices = np.asarray(svc.graph.indices, np.int64)
                src_all = np.repeat(np.arange(svc.graph.num_nodes),
                                    np.diff(indptr))
                pick = rng.choice(indices.size,
                                  size=min(16, indices.size),
                                  replace=False)
                V = svc.graph.num_nodes
                svc.apply_updates(
                    inserts=(rng.integers(0, V, 24),
                             rng.integers(0, V, 24),
                             rng.uniform(0.5, 1.5, 24)
                             .astype(np.float32)),
                    deletes=(src_all[pick], indices[pick]))
            else:
                nodes = np.arange(min(64, svc.graph.num_nodes))
                g2 = dataclasses.replace(
                    svc.graph, h=svc.graph.h * np.float32(1.5))
                svc.update_graph(g2, invalidated=nodes)
            mutated = True
        while i < len(trace) and trace[i][0] <= now:
            receipts.append(svc.submit(trace[i][1]))
            i += 1
        out = svc.step()
        served.extend(out)
        if args.sim_clock:
            dt = args.tick
            if svc.idle and i < len(trace):  # jump to the next arrival
                dt = max(dt, trace[i][0] - clock())
            clock.advance(dt)
        elif svc.idle and i < len(trace):
            time.sleep(min(0.001, max(0.0, trace[i][0] - clock())))
    return receipts, served


def serve_tcp(svc: WalkService, args) -> None:
    """The --transport tcp loop: listen, serve until a client drains
    the server (or Ctrl-C), then flush and report."""
    frontend = WalkFrontend(
        svc, FrontendConfig(host=args.host, port=args.port,
                            client_buffer=args.client_buffer,
                            slow_client=args.slow_client))
    host, port = frontend.start()
    print(f"[serve] listening on {host}:{port} "
          f"(walk_client --port {port})", flush=True)
    try:
        while not frontend.drained:
            time.sleep(0.05)
    except KeyboardInterrupt:
        print("[serve] interrupted; draining", flush=True)
    finally:
        summary = frontend.drain()
        frontend.stop()
    st = svc.stats()
    assert st.conserves(), st
    print(f"[serve] drained (flushed {summary['flushed']} partial): "
          f"{st.completed} completed, {st.expired} expired, "
          f"{st.cancelled} cancelled over {st.epochs} epochs")


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.trace == "overload" and args.max_pending > args.queries // 4:
        # make the overload trace actually overload by default
        args.max_pending = max(args.queries // 4, 1)
    gen = power_law_graph if args.graph == "powerlaw" else random_graph
    graph = gen(args.nodes, args.avg_degree, weight_dist=args.weights,
                alpha=args.alpha, seed=args.seed)
    print(f"[serve] graph: V={graph.num_nodes} E={graph.num_edges} "
          f"trace={args.trace} queries={args.queries} "
          f"clock={'sim' if args.sim_clock else 'wall'}")
    for p in args.programs.split(","):
        if p and p not in WORKLOADS:
            raise SystemExit(f"--programs: {p!r} not in "
                             f"{sorted(WORKLOADS)}")
    if args.transport == "tcp" and args.sim_clock:
        raise SystemExit("--transport tcp paces against real clients; "
                         "it needs the wall clock (drop --sim-clock)")
    clock = SimClock() if args.sim_clock else time.monotonic
    svc = WalkService(
        graph,
        ServiceConfig(slots=args.slots, epoch_len=args.epoch_len,
                      num_steps=args.steps, max_pending=args.max_pending,
                      aging_interval=args.aging_interval, seed=args.seed,
                      fairness=args.fairness, quantum=args.quantum,
                      weights=parse_tenant_weights(args.tenant_weights),
                      devices=args.devices),
        EngineConfig(method=args.method, precomp_exec=args.precomp_exec,
                     step_exec=args.step_exec,
                     rebuild_budget=args.rebuild_budget, seed=args.seed),
        clock=clock)
    if args.transport == "tcp":
        serve_tcp(svc, args)
        return
    t0 = time.time()
    trace = scripted_trace(args, graph.num_nodes)
    receipts, served = run_trace(svc, trace, args, clock)
    wall = time.time() - t0
    st = svc.stats()
    assert st.conserves(), st
    done = sum(1 for s in served if s.status == "completed")
    print(f"[serve] {st.submitted} submitted -> {st.admitted} admitted "
          f"({st.rejected_full} queue-full, {st.rejected_deadline} "
          f"deadline-infeasible, {st.rejected_unknown} unknown-program "
          f"rejected)")
    print(f"[serve] {done} completed + {st.expired} expired over "
          f"{st.epochs} epochs; peak occupancy {st.peak_occupancy}/"
          f"{st.slots} slots")
    print(f"[serve] throughput {done / max(wall, 1e-9):.0f} queries/s "
          f"(wall {wall:.2f}s); frac_rjs={st.frac_rjs:.2f} "
          f"frac_precomp={st.frac_precomp:.2f} "
          f"frac_stale={st.frac_stale:.2f} "
          f"rebuilt_rows={st.rebuilt_rows}")
    print(f"[serve] queue wait p50={st.queue_wait_p50 * 1e3:.2f}ms "
          f"p99={st.queue_wait_p99 * 1e3:.2f}ms | latency "
          f"p50={st.latency_p50 * 1e3:.2f}ms "
          f"p99={st.latency_p99 * 1e3:.2f}ms")


if __name__ == "__main__":
    main()
