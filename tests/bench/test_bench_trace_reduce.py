"""The trace reduction on hand-built events with known answers."""
import numpy as np
import pytest

from benchpath import bench_module

tr = bench_module("trace_reduce")
MS = 1_000_000  # ns


def events():
    # window 0..100 ms; device 0 runs the epoch program 10..40 and 50..60
    # (ops inside it overlap), device 1 runs it 10..70; the host is in
    # run_epoch 5..45 and admit 45..50
    return {
        "host": [("bench.window", 0, 100 * MS),
                 ("bench.run_epoch", 5 * MS, 45 * MS),
                 ("bench.admit", 45 * MS, 50 * MS),
                 ("bench.run_epoch", 50 * MS, 62 * MS)],
        "devices": {
            0: {"XLA Ops": [("fusion.1", 10 * MS, 30 * MS),
                            ("fusion.2", 25 * MS, 40 * MS),
                            ("while.3", 50 * MS, 60 * MS)],
                "XLA Modules": [("jit_epoch(123)", 10 * MS, 40 * MS),
                                ("jit_epoch(123)", 50 * MS, 60 * MS)]},
            1: {"XLA Ops": [("fusion.1", 10 * MS, 70 * MS)],
                "XLA Modules": [("jit_epoch(123)", 10 * MS, 70 * MS)]},
        },
    }


def test_busy_kernel_spans_and_gaps():
    r = tr.reduce_events(events(), {"walk": "jit_epoch"})
    assert r["window_s"] == pytest.approx(0.1)
    # device 0: union 10..40 + 50..60 = 40 ms; device 1: 60 ms
    assert r["busy_by_device_s"] == pytest.approx({0: 0.04, 1: 0.06})
    assert r["busy_s"] == pytest.approx(0.05)
    assert r["kernel_sum_s"]["walk"] == pytest.approx(0.1)
    assert r["kernel_max_s"]["walk"] == pytest.approx(0.06)
    # run_epoch spans: 40 ms with 30 ms busy, 12 ms with 10 ms busy
    assert np.allclose(r["spans"]["bench.run_epoch"],
                       [(0.04, 0.03), (0.012, 0.01)])
    gaps = dict(r["idle_gaps"])
    # idle 0..10 (host in run_epoch at 5 ms), 40..50 (admit at 45 ms),
    # 60..100 (no host span at 80 ms)
    assert gaps["bench.run_epoch"] == pytest.approx(0.010)
    assert gaps["bench.admit"] == pytest.approx(0.010)
    assert gaps["(host idle)"] == pytest.approx(0.040)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.04)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(0.04)


def test_unmatched_kernel_is_an_error():
    with pytest.raises(tr.TraceError):
        tr.reduce_events(events(), {"walk": "megastep"})


def test_union():
    iv = np.array([[5, 7], [1, 3], [2, 4], [7, 8]], float)
    assert tr.union(iv).tolist() == [[1, 4], [5, 8]]
