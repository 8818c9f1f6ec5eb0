"""Find the highest rate a served configuration sustains (its knee),
once, by a sweep on the chip, before its cell exists.

    python3 bench/sweep.py --config ppr-serve-pl20 --traffic poisson-zipf99 --rates 100,200,300 --seconds 20

It builds the configuration's service (``bench/configs/<config>.json``)
once and offers each rate in turn (the mix, skew and arrivals of
``bench/traffic/<traffic>.json``, with its rate replaced) for
``--seconds``, then lets the backlog drain.  Before each rate it admits
every admission size that rate's schedule can cause (as a run's set-up
does).
Per rate it prints one JSON line: the offered and completed rate inside
the window, the requests still unanswered when it closed, p50/p95
latency over its first and second halves — a sustained rate completes
what it is offered and its latency does not grow from one half to the
next — the compiles inside it, and each tenant's largest admission
beside the largest that set-up warmed (``admit_gap_s`` in the
configuration is set so that the second covers the first).  A served
cell's traffic file takes ``rate_qps`` at four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        config = json.loads((bench_run.BENCH / "configs"
                             / f"{args.config}.json").read_text())
        sys.path.insert(0, str(bench_run.BENCH))
        import traffic
        mix = traffic.load(args.traffic)
        if args.rehearse:
            config = bench_run.merge(config, config.get("rehearse", {}))
            mix = bench_run.merge(mix, mix.get("rehearse", {}))
        cell = {"name": f"{args.config}.{args.traffic}", "chips": 1}
        sys.path.insert(0, str(bench_run.ROOT / "src"))
        devs = bench_run.open_devices(int(cell["chips"]), args.rehearse)
    except bench_run.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return bench_run.EXIT_NO_CHIP
    if not args.rehearse:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    import graphgen
    import harness
    from drivers import served
    compiles = harness.CompileCounter()
    ctx = harness.Context(cell=cell, config=config, traffic=mix,
                          seed=args.seed, seconds=args.seconds, trace=False,
                          rehearse=args.rehearse, t_start=time.perf_counter())
    indptr, _, _, svc, fe = served.build(ctx)
    nodes = graphgen.walk_starts(indptr)
    sizes, admits = served.record_admissions(svc, sorted(config["programs"]))
    try:
        served.prewarm(ctx, fe, nodes)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            sched = traffic.open_loop(dict(mix, rate_qps=rate), nodes,
                                      args.seed + i, 0.0, args.seconds)
            k_max = min(served.largest_admission(
                sched, config["admit_gap_s"]), config["service"]["slots"])
            w0 = time.perf_counter()
            unwarmed, _ = served.warm_admissions(ctx, fe, nodes, sizes,
                                                 k_max)
            warm_s = time.perf_counter() - w0
            t0 = time.perf_counter() + 0.05
            c0, s0 = compiles.count, compiles.seconds
            client = served.LoadClient(*fe.address, sched, t0)
            client.run(until=t0 + args.seconds + config["drain_s"])
            client.close()
            due = t0 + sched["due"]
            lat = client.recv - due
            close = t0 + args.seconds
            done_in = np.isfinite(client.recv) & (client.recv <= close)
            half = sched["due"] < args.seconds / 2
            out = {"rate_qps": rate, "offered": int(due.size),
                   "completed_qps": float(done_in.sum() / args.seconds),
                   "unanswered_at_close": int((~done_in).sum()),
                   "refused": int(sum(s not in ("", "completed")
                                      for s in client.status)),
                   "compiles": compiles.count - c0,
                   "compile_s": compiles.seconds - s0,
                   "warmed_to": k_max, "unwarmed": unwarmed,
                   "warm_s": warm_s}
            for name, log in admits.items():
                out[f"admit_max_{name}"] = max(
                    (k for t, k in log if t0 <= t <= close), default=0)
            for tag, sel in (("first", half), ("second", ~half)):
                v = lat[sel & np.isfinite(lat)]
                out[f"p50_s_{tag}"] = float(np.median(v)) if v.size else None
                out[f"p95_s_{tag}"] = (float(np.percentile(v, 95))
                                       if v.size else None)
            print(json.dumps(out), flush=True)
    finally:
        fe.drain(timeout=5)
        fe.stop()
    print(json.dumps({"device": devs[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
