"""The ``dw-alias-offline-pl20`` cell's path and readers, on the CPU.

Weighted DeepWalk with ``alias_precomp`` on a small Graph500 graph,
through ``EpochScheduler`` as the offline driver drives it: the fused
mega-step (Pallas interpret mode) must give the staged scan's paths and
counters bit for bit, serve every live lane from the tables, and pass
the reference's PIT check.  The two mega-step readers find nothing
without a trace and read the floor from ``live``."""
import json

import jax
import numpy as np
import pytest

from benchpath import BENCH, bench_module

graphgen = bench_module("graphgen")
harness = bench_module("harness")
reference = bench_module("reference")
run = bench_module("run")
floor_bytes = bench_module("floor_bytes")

CONFIG = json.loads((BENCH / "configs" / "deepwalk-alias-pl20.json")
                    .read_text())
SLOTS, EPOCH, WALK = 64, 16, 40


def corpus(step_exec: str, indptr, indices, h, starts):
    """(paths, summed counters, engine) of the walks from ``starts``,
    admitted into free slots at each epoch boundary."""
    from repro.core import EngineConfig, WalkEngine
    eng = WalkEngine(harness.device_graph(indptr, indices, h),
                     harness.program(CONFIG["program"]),
                     EngineConfig(method=CONFIG["engine"]["method"],
                                  epoch_len=EPOCH, step_exec=step_exec))
    sched = eng.scheduler(num_steps=WALK, key=jax.random.key(11),
                          slots=SLOTS, epoch_len=EPOCH,
                          capacity=starts.size)
    totals = {k: 0 for k in sched.totals}
    nxt = 0
    while nxt < starts.size or sched.busy:
        free = sched.free_slots().size
        take = min(free, starts.size - nxt)
        if take:
            sched.admit(np.arange(nxt, nxt + take), starts[nxt:nxt + take])
            nxt += take
        rep = sched.run_epoch()
        for k in totals:
            totals[k] += rep.stats[k]
    return sched.paths.copy(), totals, eng


def test_fused_alias_corpus_matches_staged_and_the_reference():
    gcfg = dict(CONFIG["graph"], scale=10)
    indptr, indices, h = graphgen.make_graph(gcfg, 2**31 + 29, cache=False)
    nodes = np.nonzero(np.diff(indptr) > 0)[0]
    starts = np.random.default_rng(3).permutation(nodes)[:2 * SLOTS]
    starts = starts.astype(np.int32)
    staged, t_staged, _ = corpus("staged", indptr, indices, h, starts)
    fused, t_fused, eng = corpus("fused", indptr, indices, h, starts)
    assert eng.step_exec_resolved == "fused"
    np.testing.assert_array_equal(fused, staged)
    assert t_fused == t_staged
    # static weights: every live lane is a table draw
    assert t_fused["live"] == (fused[:, 1:] >= 0).sum() > 0
    assert t_fused["precomp_served"] == t_fused["live"]
    g = reference.Graph(indptr, indices, h)
    spec = CONFIG["program"]
    complete = np.ones(starts.size, bool)
    assert reference.form_errors(g, starts, fused) == 0
    assert reference.length_errors(spec, g, fused, complete) == 0
    rng = np.random.default_rng(5)
    prev, cur, nxt = reference.sample_hops(fused, 4000, rng)
    u = reference.pit_values(spec, g, prev, cur, nxt, rng)
    assert reference.ks_sqrt_n(u) < CONFIG["limits"]["pit_ks"]


@pytest.mark.parametrize("name", ["megastep_device_ms.offline",
                                  "megastep_roofline.offline"])
def test_megastep_readers_find_nothing_without_a_trace(name):
    rec = {"trace": None, "scan_steps": 320, "live": 10**6,
           "peaks": {"hbm_bytes_per_s": 819e9}, "floor_bytes": 0.0}
    assert run.read_metric(name, rec) is None
    assert run.read_metric(name, {}) is None


def test_megastep_readers_on_a_hand_made_record():
    tr = {"kernel_max_s": {"walk": 2.0}, "kernel_sum_s": {"walk": 3.0}}
    rec = {"trace": tr, "scan_steps": 400, "live": 5 * 10**6,
           "peaks": {"hbm_bytes_per_s": 1e9},
           # what the offline driver puts here for a deepwalk cell
           "floor_bytes": 0.0}
    assert run.read_metric("megastep_device_ms.offline", rec) == \
        pytest.approx(1e3 * 2.0 / 400)
    # the floor comes from live (20 bytes a deepwalk hop), not the record
    want = 100.0 * 20 * 5 * 10**6 / (3.0 * 1e9)
    assert floor_bytes.hop_floor_bytes("deepwalk", hops=5 * 10**6) == \
        20 * 5 * 10**6
    assert run.read_metric("megastep_roofline.offline", rec) == \
        pytest.approx(want)
    assert run.read_metric("megastep_roofline.offline",
                           dict(rec, live=0)) is None
