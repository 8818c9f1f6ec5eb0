"""The EpochScheduler's profiler spans (``sched.*``, docs/architecture.md
"Observability") as the trace reduction reads them: collected by name
with the device time inside each, on hand-built events, and found with
their nesting in a real profiler trace of a tiny engine on the CPU."""
import glob
import os

import jax
import numpy as np
import pytest

from benchpath import bench_module

tr = bench_module("trace_reduce")
MS = 1_000_000  # ns
CHILDREN = ("sched.maintain", "sched.dispatch", "sched.wait",
            "sched.harvest")
SCHED = ("sched.admit", "sched.run_epoch") + CHILDREN


def events():
    # window 0..100 ms; device 0 busy 12..40 and 52..90; two epochs, each
    # a run_epoch with its four children in order, the second running
    # past the window's end; one admit between them
    host = [("bench.window", 0, 100 * MS)]
    for t0, t1 in ((5, 45), (50, 105)):
        host.append(("sched.run_epoch", t0 * MS, t1 * MS))
        cut = np.linspace(t0, t1, 5) * MS
        host += [(n, cut[i], cut[i + 1]) for i, n in enumerate(CHILDREN)]
    host.append(("sched.admit", 46 * MS, 49 * MS))
    return {"host": host,
            "devices": {0: {"XLA Ops": [("while.1", 12 * MS, 40 * MS),
                                        ("while.1", 52 * MS, 90 * MS)],
                            "XLA Modules": [
                                ("jit_epoch_staged(1)", 12 * MS, 40 * MS),
                                ("jit_epoch_staged(1)", 52 * MS, 90 * MS)]}}}


def test_sched_spans_nest_with_the_device_time_inside():
    r = tr.reduce_events(events(), {"walk": "jit_epoch"}, span_names=SCHED)
    sp = r["spans"]
    # the second epoch crosses the window's end: only spans wholly
    # inside the window are kept
    assert np.allclose(sp["sched.run_epoch"], [(0.040, 0.028)])
    assert np.allclose(sp["sched.admit"], [(0.003, 0.0)])
    # children of the first epoch 5..15, 15..25, 25..35, 35..45 ms and
    # of the second 50..63.75, ..77.5, ..91.25, ..105 (cut); device busy
    # 12..40 and 52..90 ms
    want = {"sched.maintain": [(0.010, 0.003), (0.01375, 0.01175)],
            "sched.dispatch": [(0.010, 0.010), (0.01375, 0.01375)],
            "sched.wait": [(0.010, 0.010), (0.01375, 0.0125)],
            "sched.harvest": [(0.010, 0.005)]}
    for name, pairs in want.items():
        assert np.allclose(sp[name], pairs), name
    first = sum(sp[n][0][0] for n in CHILDREN)
    assert first <= sp["sched.run_epoch"][0][0] + 1e-12
    # the kernel pattern the configurations use still finds the renamed
    # staged epoch program
    assert r["kernel_sum_s"]["walk"] == pytest.approx(0.066)


def test_absent_sched_spans_read_as_none():
    ev = events()
    ev["host"] = [e for e in ev["host"] if not e[0].startswith("sched.")]
    r = tr.reduce_events(ev, {"walk": "jit_epoch"}, span_names=SCHED)
    assert all(r["spans"].get(n) is None for n in SCHED)


def test_a_traced_epoch_writes_the_nested_sched_spans(tmp_path):
    from repro.core import EngineConfig, WalkEngine
    from repro.graphs import random_graph
    from repro.walks import deepwalk

    eng = WalkEngine(random_graph(60, 4, seed=2), deepwalk(),
                     EngineConfig(method="ervs", tile=32, step_exec="staged"))
    sched = eng.scheduler(num_steps=4, key=jax.random.key(0), slots=8,
                          epoch_len=2, capacity=16)
    starts = np.arange(8, dtype=np.int32)
    sched.admit(np.arange(8), starts)  # compiles outside the trace
    while sched.busy:
        sched.run_epoch()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.admit(np.arange(8, 16), starts)
        sched.run_epoch()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    host = [e for e in tr.load_events(files[0])["host"]
            if e[0].startswith("sched.")]
    names = [n for n, _, _ in host]
    assert sorted(set(names)) == sorted(SCHED)
    assert names.count("sched.run_epoch") == 1
    (_, lo, hi), = [e for e in host if e[0] == "sched.run_epoch"]
    kids = sorted((s, e, n) for n, s, e in host if n in CHILDREN)
    assert [n for _, _, n in kids] == list(CHILDREN)
    assert all(lo <= s <= e <= hi for s, e, _ in kids)
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))
    assert sum(e - s for s, e, _ in kids) <= hi - lo
