"""The staged step's eRVS work counters (``StepStats.ervs_trips`` /
``ervs_edges`` / ``ervs_lane_trips``, docs/architecture.md
"Observability").

On a small graph with one planted hub, a staged ``adaptive`` engine's
per-epoch counters must equal a numpy recount: per scan step, the
reservoir lanes the cost model's routing leaves (Eq. 11), split at
``jump_threshold`` into the plain and the hub (A-ExpJ) pass, each pass
running ``ceil(longest active row / tile)`` trips and reading every
active lane's row.  Lane-trips recount the compacted passes: per shard,
chunks of ``LANE_CHUNK`` active lanes in ascending slot order, each
chunk's trips from its own longest row.  Trips and edges must not depend
on the device count, and the fused mega-step, which has no such loop,
reports 0 for all three while its paths stay bit-identical to the staged
scan's."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineConfig, WalkEngine
from repro.core import ervs
from repro.core.types import WalkerState
from repro.graphs import random_graph
from repro.graphs.csr import from_edges
from repro.walks import deepwalk, node2vec

TILE = 32
HUB = 64  # jump_threshold: rows at least this long take the hub pass
NODES, STEPS, SLOTS, EPOCH = 120, 9, 16, 4


def hub_graph(n: int = NODES, seed: int = 0):
    """Undirected: a ring and sparse random edges over nodes 1..n-1, and
    node 0 linked to all of them.  Weights U[1, 5), except node 0's own
    row: all 1 but one edge of 500, so its weight bound is far above its
    mean and Eq. 11 keeps the hub on the reservoir side."""
    rng = np.random.default_rng(seed)
    ring = np.arange(1, n)
    src = np.concatenate([ring, rng.integers(1, n, 3 * n),
                          np.zeros(n - 1, np.int64)])
    dst = np.concatenate([np.roll(ring, -1), rng.integers(1, n, 3 * n),
                          ring])
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, uniq = np.unique(src * n + dst, return_index=True)
    src, dst = src[uniq], dst[uniq]
    h = rng.uniform(1.0, 5.0, src.size).astype(np.float32)
    hub_row = np.nonzero(src == 0)[0]
    h[hub_row] = 1.0
    h[hub_row[0]] = 500.0
    return from_edges(src, dst, n, h=h)


def hub_engine(graph, **cfg):
    return WalkEngine(graph, node2vec(),
                      EngineConfig(method="adaptive", tile=TILE,
                                   jump_threshold=HUB, step_exec="staged",
                                   **cfg))


def drive(eng, starts, devices=None):
    """Stream ``starts`` through a scheduler; returns it and, per epoch,
    (slot -> query before the epoch, steps before it, epoch stats)."""
    sched = eng.scheduler(num_steps=STEPS, key=jax.random.key(7),
                          slots=SLOTS, epoch_len=EPOCH, capacity=len(starts),
                          devices=devices)
    pending, epochs = list(range(len(starts))), []
    while pending or sched.busy:
        n = sched.free_slots().size
        take, pending = pending[:n], pending[n:]
        sched.admit(take, starts[take])
        slot_q, step0 = sched.slot_query.copy(), np.asarray(sched.state.step)
        rep = sched.run_epoch()
        epochs.append((slot_q, step0, rep.stats))
    return sched, epochs


def pass_work(deg: np.ndarray, max_tiles: int):
    """(trips, edges) of one tile-loop pass over lanes of degree ``deg``."""
    if deg.size == 0:
        return 0, 0
    trips = min(-(-int(deg.max()) // TILE), max_tiles)
    return trips, int(np.minimum(deg, trips * TILE).sum())


def lane_trips(deg: np.ndarray, slots: np.ndarray, max_tiles: int,
               shards: int):
    """Lane-trips of one compacted pass over lanes of degree ``deg`` in
    the ascending ``slots``: per shard, chunks of K active lanes, each
    chunk running its own longest row's trips on ``shards · K`` lanes (or
    the dense pass, trips on every slot, where K is a whole shard)."""
    spd = SLOTS // shards
    k = min(ervs.LANE_CHUNK, spd)
    if k == spd:
        return pass_work(deg, max_tiles)[0] * SLOTS
    per = [deg[slots // spd == d] for d in range(shards)]
    return sum(
        pass_work(np.concatenate([p[c:c + k] for p in per]), max_tiles)[0]
        * shards * k
        for c in range(0, max(p.size for p in per), k))


def recount(eng, paths, slot_q, step0, shards=1):
    """Numpy recount of one epoch's (trips, edges, steps with a hub pass,
    lane-trips) from the lanes' positions and the cost model's routing."""
    deg_all = np.diff(np.asarray(eng.graph.indptr))
    occ = np.nonzero(slot_q >= 0)[0]
    trips = edges = hub_steps = lanes = 0
    for j in range(EPOCH):
        # every row is non-empty, so no walk dead-ends: a lane is live
        # while its walk has steps left
        q, s = slot_q[occ], step0[occ] + j
        slots = occ[s < STEPS]
        q, s = q[s < STEPS], s[s < STEPS]
        if q.size == 0:
            continue
        cur = paths[q, s]
        prev = np.where(s > 0, paths[q, np.maximum(s - 1, 0)], -1)
        qs = jnp.asarray(q, jnp.int32)
        state = WalkerState(
            cur=jnp.asarray(cur, jnp.int32), prev=jnp.asarray(prev, jnp.int32),
            step=jnp.asarray(s, jnp.int32), alive=jnp.ones(q.size, bool),
            rng=jnp.zeros((q.size, 2), jnp.uint32), carry=None,
            wstate=eng.workload.init_wstate_batch(qs))
        est = eng.sampler_ctx.estimates(state)
        deg = deg_all[cur]
        rjs = np.asarray(eng.config.cost_model.prefer_rjs(
            est.bound_max, est.sum_est, jnp.asarray(deg)))
        hi = ~rjs & (deg >= HUB)
        lo = ~rjs & ~hi
        hub_steps += int(hi.any())
        for mask in (lo, hi):
            t, e = pass_work(deg[mask], eng.max_tiles)
            trips, edges = trips + t, edges + e
            lanes += lane_trips(deg[mask], slots[mask], eng.max_tiles,
                                shards)
    return trips, edges, hub_steps, lanes


def test_counters_match_a_numpy_recount():
    graph = hub_graph()
    eng = hub_engine(graph)
    starts = np.arange(40, dtype=np.int32) % NODES  # node 0 among them
    sched, epochs = drive(eng, starts)
    hub_steps = 0
    for slot_q, step0, stats in epochs:
        # with no rejection fallbacks, the reservoir side is exactly the
        # lanes the policy kept
        assert stats["fallbacks"] == 0
        trips, edges, h, lanes = recount(eng, sched.paths, slot_q, step0)
        assert (stats["ervs_trips"], stats["ervs_edges"]) == (trips, edges)
        # a pool of SLOTS <= LANE_CHUNK runs the dense passes
        assert stats["ervs_lane_trips"] == lanes == trips * SLOTS
        # invariants: a trip reads at most tile entries of every slot, and
        # a step runs at most one full hub pass plus one plain pass
        assert stats["ervs_edges"] <= stats["ervs_trips"] * TILE * SLOTS
        row = int(graph.max_degree())
        assert stats["ervs_trips"] <= EPOCH * (-(-row // TILE)
                                               + -(-(HUB - 1) // TILE))
        hub_steps += h
    # the recount saw both routings and both reservoir passes
    assert 0 < sched.totals["rjs_served"] < sched.totals["live"]
    assert hub_steps > 0
    assert sched.totals["ervs_trips"] > hub_steps


def test_lane_trips_match_a_numpy_recount(monkeypatch):
    """With chunks of 4 lanes the passes compact: lane-trips fall below
    the dense ``trips × slots`` and equal their recount, while paths and
    every other counter stay those of the dense passes."""
    graph = hub_graph()
    starts = np.arange(40, dtype=np.int32) % NODES
    dense, _ = drive(hub_engine(graph), starts)
    monkeypatch.setattr(ervs, "LANE_CHUNK", 4)
    eng = hub_engine(graph)
    sched, epochs = drive(eng, starts)
    for slot_q, step0, stats in epochs:
        *_, lanes = recount(eng, sched.paths, slot_q, step0)
        assert stats["ervs_lane_trips"] == lanes
    np.testing.assert_array_equal(dense.paths, sched.paths)
    # the dist searches follow the lane-trips; the eRJS trials' stay equal
    trials = [s.totals.pop("dist_searches") - s.totals["ervs_lane_trips"]
              * TILE for s in (dense, sched)]
    assert trials[0] == trials[1] > 0
    lane_trips = sched.totals.pop("ervs_lane_trips")
    assert 0 < lane_trips < sched.totals["ervs_trips"] * SLOTS
    assert dense.totals.pop("ervs_lane_trips") \
        == dense.totals["ervs_trips"] * SLOTS
    assert dense.totals == sched.totals


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {tests!r})
import jax
import numpy as np
import test_observability as t

assert len(jax.devices()) == 4, jax.devices()
eng = t.hub_engine(t.hub_graph())
starts = np.arange(40, dtype=np.int32) % t.NODES
one, _ = t.drive(eng, starts)
four, _ = t.drive(eng, starts, devices=4)
np.testing.assert_array_equal(one.paths, four.paths)
assert one.totals == four.totals, (one.totals, four.totals)
assert one.totals["ervs_trips"] > 0
# chunks of 2 lanes: each device's 4 slots compact on their own, and the
# lane-trips follow the chunking of each device count
t.ervs.LANE_CHUNK = 2
for devices, shards in ((None, 1), (4, 4)):
    eng = t.hub_engine(t.hub_graph())
    sched, epochs = t.drive(eng, starts, devices=devices)
    for slot_q, step0, stats in epochs:
        *_, lanes = t.recount(eng, sched.paths, slot_q, step0, shards)
        assert stats["ervs_lane_trips"] == lanes, (shards, stats, lanes)
    np.testing.assert_array_equal(one.paths, sched.paths)
    # the dist searches follow the lane-trips; the eRJS trials' stay equal
    assert sched.totals.pop("dist_searches") \
        - sched.totals["ervs_lane_trips"] * t.TILE \
        == one.totals["dist_searches"] - one.totals["ervs_lane_trips"] * t.TILE
    lane_trips = sched.totals.pop("ervs_lane_trips")
    assert 0 < lane_trips < sched.totals["ervs_trips"] * t.SLOTS
    rest = dict(one.totals)
    del rest["ervs_lane_trips"], rest["dist_searches"]
    assert rest == sched.totals, (shards, rest, sched.totals)
print("COUNTERS-X4-OK", one.totals)
"""


def test_counter_totals_match_across_four_devices():
    src = _CHILD.format(tests=str(Path(__file__).resolve().parent))
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(root / "src"),
             "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert "COUNTERS-X4-OK" in out.stdout, out.stdout + out.stderr


def test_fused_mega_step_reports_zero_and_matches_staged_paths():
    graph = random_graph(40, 4, seed=3)
    starts = np.arange(8, dtype=np.int32)
    runs = {}
    for exec_ in ("staged", "fused"):
        eng = WalkEngine(graph, deepwalk(),
                         EngineConfig(method="ervs", tile=TILE,
                                      step_exec=exec_))
        assert eng.step_exec_resolved == exec_
        sched = eng.scheduler(num_steps=6, key=jax.random.key(1), slots=8,
                              epoch_len=3)
        sched.admit(np.arange(8), starts)
        while sched.busy:
            sched.run_epoch()
        runs[exec_] = sched
    st, fu = runs["staged"], runs["fused"]
    np.testing.assert_array_equal(st.paths, fu.paths)
    assert st.totals["ervs_trips"] > 0 and st.totals["ervs_edges"] > 0
    assert fu.totals["ervs_trips"] == fu.totals["ervs_edges"] == 0
    assert st.totals["ervs_lane_trips"] == st.totals["ervs_trips"] * 8
    assert fu.totals["ervs_lane_trips"] == 0
    lanes = {k: v for k, v in st.totals.items() if not k.startswith("ervs")}
    assert lanes == {k: fu.totals[k] for k in lanes}
