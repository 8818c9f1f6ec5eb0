"""Smoke run of the walk engine's main path on a TPU chip.

    python chip_smoke.py               # phases A-D on one chip
    python chip_smoke.py --four-chips  # the devices=4 paths vs devices=1
    python chip_smoke.py --rehearse    # tiny CPU dry run (never exits 0)

One seeded ``power_law_graph(2**20, 16)`` is built once and reused.  Each
phase goes through the entry points a user calls (``WalkEngine.run``,
``WalkService``, the TCP front-end) and checks its result against what
the repository treats as ground truth:

A. node2vec / ``adaptive`` (staged), offline: every hop is an edge of
   the graph; first-hop frequencies match ``exact_probs`` (chi-square).
B. deepwalk / ``ervs`` and ``erjs`` with ``step_exec="auto"``: resolves to
   the compiled fused mega-step, byte-identical to ``step_exec="staged"``
   (the staged twin walks the first queries for the first steps only:
   streams are keyed per query and step, so those walks are prefixes of
   the full run's).
C. deepwalk / ``its_precomp`` and ``alias_precomp`` with
   ``precomp_exec="auto"``: resolves to the Pallas table kernels over the
   aligned streams, bit-identical to ``precomp_exec="jnp"``; with
   ``step_exec="auto"`` too, resolves to the mega-step's table regime,
   byte-identical to the staged run.
D. ``WalkService`` (deepwalk + ppr_nibble tenants, real clock): bursts of
   queries, a few dozen over TCP loopback; all complete, the counters
   conserve, and served paths equal the offline ``WalkEngine.run``.

``--four-chips`` runs only A with ``devices=4`` against ``devices=1``, and
D with one ``ServiceConfig(devices=4)`` deepwalk/eRJS tenant against the
offline run.

Each phase prints its set-up, first-call, backend-compile and wall times
and the device's peak memory.  These are smoke readings, not benchmark
numbers.  Any failed check, a platform other than TPU, or a path that
resolved to staged/jnp/interpret where the phase demands the kernel ends
the script with a traceback and a non-zero exit.  The last line is
``{"ok": true, "device": {...}}``.  Compiled programs are cached in
``JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: full sizes (one v5e chip), the --four-chips run's and the CPU
#: rehearsal's.  The staged scan's step costs the largest active row of
#: the pool on every lane, and the 2^20 graph's hub has ~117k edges (about
#: 6 s per staged eRVS step on one v5e), so the staged runs (phase A, and
#: B's twin: ``b_twin`` queries x ``b_twin_steps``; the sharded served
#: tenant, whose slot pool runs staged: ``d_method`` eRJS, whose staged
#: step seldom enters the reservoir's tile loop, for ``d_steps`` per
#: query) are sized to fit the whole smoke in well under 20 minutes; fused
#: and table paths take full widths (``d_steps=None``: the program's
#: walk_len).
FULL = dict(log2_nodes=20, steps=80, a_queries=8192, a_batch=4096,
            b_queries=16384, b_twin=1024, b_twin_steps=12, c_queries=16384,
            d_queries=2048, d_tcp=48, d_slots=1024, d_method="ervs",
            d_steps=None, chi_reps=1024)
FOUR = dict(FULL, a_queries=2048, a_batch=2048, chi_reps=256,
            d_queries=256, d_tcp=16, d_method="erjs", d_steps=12)
REHEARSE = dict(log2_nodes=12, steps=12, a_queries=6144, a_batch=1024,
                b_queries=256, b_twin=128, b_twin_steps=6, c_queries=256,
                d_queries=192, d_tcp=16, d_slots=64, d_method="ervs",
                d_steps=None, chi_reps=1024)
SEED = 0


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the devices=4 paths (phase A's node2vec "
                         "run and a served tenant) against devices=1")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny run on any backend (kernels in interpret "
                         "mode); checks everything, prints no result and "
                         "exits 3")
    return ap.parse_args()


# ------------------------------------------------------------ readings
class Phase:
    """Times one phase: set-up (engine/table build), the first call
    (compile + run), XLA backend-compile seconds, wall time, and the
    device's peak bytes in use so far."""

    compile_s = 0.0

    def __init__(self, name: str, dev):
        self.name, self.dev = name, dev
        self.readings = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = Phase.compile_s
        return self

    def mark(self, what: str, t_start: float) -> float:
        dt = time.perf_counter() - t_start
        self.readings[what] = self.readings.get(what, 0.0) + dt
        return dt

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        stats = self.dev.memory_stats() or {}
        parts = [f"{k}={v:.2f}" for k, v in self.readings.items()]
        parts.append(f"compile_s={Phase.compile_s - self.c0:.2f}")
        parts.append(f"wall_s={time.perf_counter() - self.t0:.2f}")
        parts.append(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
        print(f"[smoke] phase {self.name}: " + " ".join(parts), flush=True)
        return False


def _on_duration(event: str, duration: float, **_):
    if event.endswith("backend_compile_duration"):
        Phase.compile_s += duration


# --------------------------------------------------------------- checks
def check_hops(indptr, indices, paths) -> int:
    """Every emitted hop u→v is an edge of the CSR graph, and a walk never
    resumes after its -1 terminator.  Returns the number of hops."""
    import numpy as np
    assert ((paths[:, :-1] >= 0) | (paths[:, 1:] < 0)).all(), \
        "a walk resumed after terminating"
    src, dst = paths[:, :-1].ravel(), paths[:, 1:].ravel()
    live = dst >= 0
    V = indptr.shape[0] - 1
    keys = (np.repeat(np.arange(V, dtype=np.int64), np.diff(indptr)) * V
            + indices)  # rows are sorted, so keys are too
    q = src[live].astype(np.int64) * V + dst[live]
    pos = np.minimum(np.searchsorted(keys, q), keys.shape[0] - 1)
    bad = int((keys[pos] != q).sum())
    assert bad == 0, f"{bad} emitted hops are not edges of the graph"
    return int(live.sum())


def chi2_first_hop(out, p, nbr):
    """(chi-square statistic, Wilson–Hilferty critical value at z=3.7)
    of sampled next nodes ``out`` against the exact distribution."""
    import numpy as np
    sup = (nbr >= 0) & (p > 0)
    support, probs = nbr[sup], p[sup] / p[sup].sum()
    assert np.isin(out, support).all(), "first hop outside the support"
    counts = (out[:, None] == support[None, :]).sum(axis=0)
    expected = probs * out.shape[0]
    df = support.shape[0] - 1
    a = 2.0 / (9.0 * df)
    return (float(((counts - expected) ** 2 / expected).sum()),
            df * (1.0 - a + 3.7 * np.sqrt(a)) ** 3)


def same_result(a, b, what: str):
    import numpy as np
    assert np.array_equal(a.paths, b.paths), f"{what}: paths differ"
    for f in ("frac_rjs", "frac_precomp", "frac_stale", "rjs_fallbacks",
              "live_steps"):
        assert getattr(a, f) == getattr(b, f), \
            f"{what}: {f} {getattr(a, f)} != {getattr(b, f)}"


def timed_run(ph, eng, starts, steps, key, **kw):
    import jax
    t = time.perf_counter()
    res = eng.run(starts, num_steps=steps, key=jax.random.key(key), **kw)
    dt = ph.mark("run_s", t)
    print(f"[smoke]   {ph.name}: {eng.config.method} "
          f"step_exec={eng.step_exec_resolved} "
          f"precomp_exec={eng.config.precomp_exec} {kw or ''} "
          f"run {dt:.2f}s", flush=True)
    return res


# --------------------------------------------------------------- phases
def phase_a(g, cfg, dev, rng, devices=None):
    """node2vec / adaptive, staged, offline; with ``devices`` also the
    same run sharded over that many chips (must be bit-identical)."""
    import numpy as np
    from repro.core import EngineConfig, WalkEngine
    from repro.core.runtime import exact_probs
    from repro.walks import node2vec

    deg = np.asarray(g.degrees())
    with Phase("A" if devices is None else f"A x{devices}", dev) as ph:
        t = time.perf_counter()
        wl = node2vec()
        eng = WalkEngine(g, wl, EngineConfig(method="adaptive",
                                             seed=SEED))
        ph.mark("setup_s", t)
        assert eng.step_exec_resolved == "staged", eng.step_exec_resolved
        # a few mid-degree start nodes repeated for the chi-square check,
        # the rest of the queries from uniformly random starts
        chi = rng.choice(np.nonzero((deg >= 8) & (deg <= 48))[0], 4,
                         replace=False)
        reps = cfg["chi_reps"]
        starts = np.concatenate([
            np.repeat(chi, reps),
            rng.integers(0, g.num_nodes, cfg["a_queries"] - 4 * reps)
        ]).astype(np.int32)
        res = timed_run(ph, eng, starts, cfg["steps"], 11,
                        batch=cfg["a_batch"])
        hops = check_hops(np.asarray(g.indptr), np.asarray(g.indices),
                          res.paths)
        for i, v in enumerate(chi):
            p, nbr = exact_probs(g, wl, wl.params(), int(v), -1, 0, eng.pad)
            first = res.paths[i * reps:(i + 1) * reps, 1]
            stat, crit = chi2_first_hop(first, p, nbr)
            assert stat < crit, f"node {v}: chi2 {stat:.1f} >= {crit:.1f}"
        if devices is not None:
            sharded = timed_run(ph, eng, starts, cfg["steps"], 11,
                                batch=cfg["a_batch"], devices=devices)
            same_result(res, sharded, f"node2vec devices={devices}")
            print(f"[smoke] A x{devices}: per-device "
                  f"{sharded.per_device}", flush=True)
        print(f"[smoke] A: {hops} hops checked, frac_rjs={res.frac_rjs:.3f}",
              flush=True)


def phase_b(g, cfg, dev, rng, interpret_ok):
    import numpy as np
    from repro.core import EngineConfig, WalkEngine
    from repro.kernels.precomp_kernel import default_interpret
    from repro.walks import deepwalk

    starts = rng.integers(0, g.num_nodes, cfg["b_queries"]).astype(np.int32)
    twin = starts[:cfg["b_twin"]]
    for method in ("ervs", "erjs"):
        with Phase(f"B {method}", dev) as ph:
            t = time.perf_counter()
            fu = WalkEngine(g, deepwalk(), EngineConfig(
                method=method, seed=SEED,
                step_exec="fused" if interpret_ok else "auto"))
            st = WalkEngine(g, deepwalk(), EngineConfig(
                method=method, seed=SEED, step_exec="staged"))
            ph.mark("setup_s", t)
            assert fu.step_exec_resolved == "fused", fu.fuse.reasons
            assert interpret_ok or default_interpret() is False
            full = timed_run(ph, fu, starts, cfg["steps"], 22)
            check_hops(np.asarray(g.indptr), np.asarray(g.indices),
                       full.paths)
            n, k = twin.shape[0], cfg["b_twin_steps"]
            a = timed_run(ph, fu, twin, k, 22)
            assert np.array_equal(a.paths, full.paths[:n, :k + 1]), \
                f"{method}: the twin's walks are not prefixes of the run's"
            b = timed_run(ph, st, twin, k, 22)
            same_result(a, b, f"{method} fused vs staged")


def phase_c(g, cfg, dev, rng, interpret_ok):
    import numpy as np
    from repro.core import EngineConfig, WalkEngine
    from repro.core.samplers import resolve_precomp_exec
    from repro.walks import deepwalk

    starts = rng.integers(0, g.num_nodes, cfg["c_queries"]).astype(np.int32)
    auto = "pallas" if interpret_ok else "auto"
    assert resolve_precomp_exec(auto) == "pallas"
    for method, streams in (("its_precomp", ("cdf2d",)),
                            ("alias_precomp", ("prob2d", "alias2d"))):
        with Phase(f"C {method}", dev) as ph:
            t = time.perf_counter()
            pk = WalkEngine(g, deepwalk(), EngineConfig(
                method=method, seed=SEED, step_exec="staged",
                precomp_exec=auto))
            ph.mark("setup_s", t)
            assert all(getattr(pk.precomp, f) is not None
                       for f in streams + ("arow0",)), "aligned streams"
            t = time.perf_counter()
            jn = WalkEngine(g, deepwalk(), EngineConfig(
                method=method, seed=SEED, step_exec="staged",
                precomp_exec="jnp"))
            # the default path: the mega-step's table regime
            fu = WalkEngine(g, deepwalk(), EngineConfig(
                method=method, seed=SEED,
                step_exec="fused" if interpret_ok else "auto"))
            ph.mark("setup_s", t)
            assert fu.step_exec_resolved == "fused", fu.fuse.reasons
            a = timed_run(ph, pk, starts, cfg["steps"], 33)
            b = timed_run(ph, jn, starts, cfg["steps"], 33)
            same_result(a, b, f"{method} pallas vs jnp")
            c = timed_run(ph, fu, starts, cfg["steps"], 33)
            same_result(c, a, f"{method} fused vs staged")
            assert a.frac_precomp == 1.0, a.frac_precomp
            check_hops(np.asarray(g.indptr), np.asarray(g.indices), a.paths)


def phase_d(g, cfg, dev, rng, interpret_ok, devices=1):
    import numpy as np
    from repro.core import EngineConfig
    from repro.launch.walk_client import WalkServiceClient
    from repro.serving import (ServiceConfig, WalkFrontend, WalkQuery,
                               WalkService)

    programs = ("deepwalk", "ppr_nibble") if devices == 1 else ("deepwalk",)
    name = "D" if devices == 1 else f"D x{devices}"
    with Phase(name, dev) as ph:
        t = time.perf_counter()
        svc = WalkService(
            g, ServiceConfig(slots=cfg["d_slots"], epoch_len=8, seed=SEED,
                             devices=devices, num_steps=cfg["d_steps"]),
            EngineConfig(method=cfg["d_method"],
                         step_exec="fused" if interpret_ok else "auto"))
        for p in programs:
            eng = svc.tenant(p).engine
            # sharded slot pools run the staged scan by design
            assert devices > 1 or eng.step_exec_resolved == "fused", p
        ph.mark("setup_s", t)
        n_direct = cfg["d_queries"] - cfg["d_tcp"]
        order = {p: [] for p in programs}  # per-tenant submission order
        served = {}
        t = time.perf_counter()
        for burst in np.array_split(np.arange(n_direct), 8):
            for _ in burst:
                p = programs[int(rng.integers(0, len(programs)))]
                s = int(rng.integers(0, g.num_nodes))
                r = svc.submit(WalkQuery(start=s, program=p))
                assert r.accepted, r
                order[p].append((r.ticket, s))
            for _ in range(2):
                served.update((w.ticket, w) for w in svc.step())
        served.update((w.ticket, w) for w in svc.drain())
        ph.mark("direct_s", t)
        t = time.perf_counter()
        fe = WalkFrontend(svc)
        host, port = fe.start()
        try:
            with WalkServiceClient(host=host, port=port, timeout=600) as c:
                for i, p in enumerate(programs):
                    share = cfg["d_tcp"] // len(programs)
                    starts = rng.integers(0, g.num_nodes, share)
                    for w, s in zip(c.walk(starts, program=p), starts):
                        served[w.ticket] = w
                        order[p].append((w.ticket, int(s)))
        finally:
            fe.drain()
            fe.stop()
        ph.mark("tcp_s", t)
        st = svc.stats()
        assert st.conserves(), st
        assert st.completed == cfg["d_queries"] == len(served), st
        assert all(w.status == "completed" for w in served.values())
        t = time.perf_counter()
        for p in programs:
            tenant = svc.tenant(p)
            tickets, starts = zip(*order[p])
            ref = tenant.engine.run(np.asarray(starts, np.int32),
                                    num_steps=tenant.num_steps,
                                    key=tenant.key).paths
            got = np.stack([served[k].path for k in tickets])
            assert np.array_equal(got, ref), f"served {p} != offline run"
            check_hops(np.asarray(g.indptr), np.asarray(g.indices), got)
        ph.mark("offline_s", t)
        print(f"[smoke] {name}: {st.completed} served "
              f"({cfg['d_tcp']} over TCP), p99 latency "
              f"{st.latency_p99:.3f}s", flush=True)


# ----------------------------------------------------------------- main
def main():
    t0 = time.perf_counter()
    args = parse_args()
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); this smoke only runs on the chip")
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        sys.exit(f"chip_smoke: needs {want} devices, JAX has "
                 f"{len(jax.devices())}")
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.graphs import power_law_graph
    except ImportError as e:
        sys.exit(f"chip_smoke: the repository's src/ is not beside this "
                 f"script ({e})")
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cfg = (REHEARSE if args.rehearse else
           FOUR if args.four_chips else FULL)
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())} "
          f"({dev.platform}), compile cache {cache}; smoke readings, "
          f"not benchmark numbers", flush=True)

    t = time.perf_counter()
    g = power_law_graph(1 << cfg["log2_nodes"], 16, weight_dist="uniform",
                        seed=SEED)
    jax.block_until_ready(g.indices)
    print(f"[smoke] graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"max degree {int(g.max_degree())}, generated+uploaded in "
          f"{time.perf_counter() - t:.2f}s", flush=True)
    rng = np.random.default_rng(SEED)
    if args.four_chips:
        phase_a(g, cfg, dev, rng, devices=4)
        phase_d(g, cfg, dev, rng, args.rehearse, devices=4)
    else:
        phase_a(g, cfg, dev, rng)
        phase_b(g, cfg, dev, rng, args.rehearse)
        phase_c(g, cfg, dev, rng, args.rehearse)
        phase_d(g, cfg, dev, rng, args.rehearse)
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.2f}s "
          f"(process start to here)", flush=True)
    if args.rehearse:
        print("[smoke] rehearsal passed; no result on a rehearsal",
              flush=True)
        sys.exit(3)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
