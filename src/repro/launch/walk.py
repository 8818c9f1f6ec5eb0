"""Walk launcher — the paper's primary entry point.

    PYTHONPATH=src python -m repro.launch.walk --workload node2vec \
        --nodes 20000 --avg-degree 12 --queries 2048 --steps 40 \
        --method adaptive

Multi-device (docs/scaling.md): ``--devices N`` shards the scheduler's
slot pool over a 1D walker mesh and prints per-device telemetry.  On a
CPU-only host, force N host devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python -m repro.launch.walk --devices 2 ...
"""
from __future__ import annotations

import argparse
import ast
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (EngineConfig, WalkEngine, available_samplers,
                        profile_edge_cost_ratio)
from repro.core.cost_model import CostModel
from repro.core.runtime import STEP_EXEC_CHOICES
from repro.core.samplers import PRECOMP_EXEC_CHOICES
from repro.graphs import power_law_graph, random_graph
from repro.walks import WORKLOADS, make_workload


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface, as one inspectable object.

    ``tools/check_docs.py`` cross-checks every ``--flag`` the docs show in
    a ``repro.launch.walk`` command against this parser, so a removed or
    renamed flag fails the docs gate instead of rotting silently.
    """
    ap = argparse.ArgumentParser(prog="repro.launch.walk")
    ap.add_argument("--workload", choices=sorted(WORKLOADS), default="node2vec")
    ap.add_argument("--list-workloads", action="store_true",
                    help="print the registered workload names (one per "
                         "line, sorted like the registry) and exit")
    ap.add_argument("--workload-arg", action="append", default=[],
                    metavar="KEY=VALUE", dest="workload_arg",
                    help="factory keyword for the selected workload, e.g. "
                         "--workload-arg a=4.0 --workload-arg window=32 "
                         "(values parsed as Python literals, falling back "
                         "to strings; repeatable)")
    # choices come from the sampler registry, so plugin samplers registered
    # before main() runs are selectable from the CLI too.
    ap.add_argument("--method", choices=available_samplers(),
                    default="adaptive")
    ap.add_argument("--precomp-exec", choices=list(PRECOMP_EXEC_CHOICES),
                    default="auto",
                    help="execution path for precomputed-table draws: the "
                         "Pallas DMA kernels or the jnp selectors "
                         "(bit-identical; auto = pallas on TPU)")
    ap.add_argument("--step-exec", choices=list(STEP_EXEC_CHOICES),
                    default="auto",
                    help="step execution path: the fused Pallas mega-step "
                         "kernel or the staged lax.scan loop (bit-identical; "
                         "auto = fused on TPU when the sampler × workload "
                         "cell is provably fusable, staged otherwise)")
    ap.add_argument("--rebuild-budget", type=int, default=8,
                    help="stale precomp table rows re-baked per scheduler "
                         "epoch after a weight mutation (0 disables the "
                         "amortized background rebuild)")
    ap.add_argument("--batch", type=int, default=None,
                    help="walker slots for the streaming scheduler "
                         "(default: all queries at once)")
    ap.add_argument("--epoch-len", type=int, default=None,
                    help="scan steps between host-side slot refills")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the slot pool over this many local devices "
                         "(1D walker mesh; results are bit-identical to a "
                         "single-device run — see docs/scaling.md)")
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--avg-degree", type=int, default=12)
    ap.add_argument("--graph", choices=["random", "powerlaw"],
                    default="powerlaw")
    ap.add_argument("--weights", choices=["uniform", "pareto", "degree",
                                          "ones"], default="uniform")
    ap.add_argument("--alpha", type=float, default=2.0)
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--profile", action="store_true",
                    help="profile the EdgeCost ratio first (§5.1)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def parse_workload_args(pairs) -> dict:
    """``--workload-arg key=value`` pairs as a factory-kwargs dict.

    Values go through ``ast.literal_eval`` (ints, floats, bools, tuples —
    e.g. ``schema=(0,1,2)``); anything that does not parse stays a string.
    """
    kw = {}
    for pair in pairs:
        key, sep, value = pair.partition("=")
        if not sep or not key:
            raise SystemExit(
                f"--workload-arg expects KEY=VALUE, got {pair!r}")
        try:
            kw[key] = ast.literal_eval(value)
        except (ValueError, SyntaxError):
            kw[key] = value
    return kw


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.list_workloads:
        for name in sorted(WORKLOADS):
            print(name)
        return

    gen = power_law_graph if args.graph == "powerlaw" else random_graph
    graph = gen(args.nodes, args.avg_degree, weight_dist=args.weights,
                alpha=args.alpha, seed=args.seed)
    print(f"[walk] graph: V={graph.num_nodes} E={graph.num_edges} "
          f"maxdeg={graph.max_degree()}")
    wl = make_workload(args.workload, **parse_workload_args(args.workload_arg))
    cm = CostModel()
    if args.profile:
        t0 = time.time()
        ratio = profile_edge_cost_ratio(graph)
        cm = CostModel(edge_cost_ratio=ratio)
        print(f"[walk] profiled EdgeCost ratio = {ratio:.2f} "
              f"({time.time()-t0:.2f}s)")
    eng = WalkEngine(graph, wl, EngineConfig(
        method=args.method, cost_model=cm, seed=args.seed,
        precomp_exec=args.precomp_exec, step_exec=args.step_exec,
        rebuild_budget=args.rebuild_budget))
    print(f"[walk] compiler flag: {eng.compiled.flag} "
          f"warnings={eng.compiled.warnings} "
          f"step_exec={eng.step_exec_resolved}")
    starts = np.arange(args.queries) % graph.num_nodes
    t0 = time.time()
    res = eng.run(starts, num_steps=args.steps, batch=args.batch,
                  epoch_len=args.epoch_len, devices=args.devices)
    dt = time.time() - t0
    total_steps = int((res.paths[:, 1:] >= 0).sum())
    print(f"[walk] {args.queries} queries × {res.steps} steps in {dt:.2f}s "
          f"({total_steps / dt:.0f} steps/s) frac_rjs={res.frac_rjs:.2f} "
          f"frac_precomp={res.frac_precomp:.2f} "
          f"frac_stale={res.frac_stale:.2f} "
          f"(over {res.live_steps} live steps) "
          f"fallbacks={res.rjs_fallbacks} "
          f"rebuilt_rows={res.rebuilt_rows}")
    if res.per_device is not None:
        for d in res.per_device:
            print(f"[walk]   device {d['device']}: {d['slots']} slots, "
                  f"{d['queries']} queries, "
                  f"{d['emitted_steps']} emitted steps")


if __name__ == "__main__":
    main()
