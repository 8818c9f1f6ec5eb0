"""Topology invariance of the sharded streaming scheduler (docs/scaling.md):
walks on a forced 2-device host mesh must be bit-identical to single-device
execution — same paths, same telemetry — for the reservoir (`ervs`),
three-regime (`adaptive`) and pipelined (`interleaved`) samplers, including
mid-epoch refills from the host queue.  XLA device-count forcing must
happen before jax is imported, so the mesh cases run in a subprocess (the
same pattern as TestShardingRules in test_system.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, WalkEngine
from repro.distributed import walker_mesh, walker_spec
from repro.graphs import random_graph
from repro.walks import deepwalk

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import EngineConfig, WalkEngine
from repro.distributed import shard_walker_state, walker_mesh, walker_spec
from repro.graphs import random_graph
from repro.walks import node2vec

assert len(jax.devices()) == 2, jax.devices()
g = random_graph(200, 8, seed=1)
key = jax.random.key(3)
for method in ["ervs", "adaptive", "interleaved"]:
    eng = WalkEngine(g, node2vec(), EngineConfig(method=method, tile=64))
    # 13 queries through 4 slots with 2-step epochs: forces several
    # mid-walk refills, and 13 % 4 != 0 leaves a partial tail epoch.
    one = eng.run(np.arange(13), num_steps=9, key=key,
                  batch=4, epoch_len=2, devices=1)
    two = eng.run(np.arange(13), num_steps=9, key=key,
                  batch=4, epoch_len=2, devices=2)
    full = eng.run(np.arange(13), num_steps=9, key=key)
    np.testing.assert_array_equal(one.paths, two.paths, err_msg=method)
    np.testing.assert_array_equal(full.paths, two.paths, err_msg=method)
    assert one.frac_rjs == two.frac_rjs, method
    assert one.frac_precomp == two.frac_precomp, method
    assert one.live_steps == two.live_steps == 13 * 9, method
    assert one.rjs_fallbacks == two.rjs_fallbacks, method
    # per-device telemetry: present only when sharded, covers all queries,
    # and the round-robin refill kept both devices fed (13 -> 7/6 split)
    assert one.per_device is None, method
    assert [d["device"] for d in two.per_device] == [0, 1], method
    assert sum(d["queries"] for d in two.per_device) == 13, method
    assert min(d["queries"] for d in two.per_device) >= 6, method
    assert sum(d["emitted_steps"] for d in two.per_device) == 13 * 9, method
    # walk_batch: the no-scheduler entry point under an explicit mesh
    p1, s1 = eng.walk_batch(np.arange(8, dtype=np.int32), key, 6)
    p2, s2 = eng.walk_batch(np.arange(8, dtype=np.int32), key, 6, devices=2)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2),
                                  err_msg=method)
    assert int(np.asarray(s1.live).sum()) == int(np.asarray(s2.live).sum())

# adaptive node2vec with its reservoir passes compacted, in chunks of 2 of
# each device's 8 slots (one rejection trial a step leaves the reservoir
# side many fallbacks): still bit-identical to one device, and the
# compiled sharded epoch moves no lane between the devices
import re
from repro.core import ervs
ervs.LANE_CHUNK = 2
eng = WalkEngine(g, node2vec(), EngineConfig(method="adaptive", tile=64,
                                             rjs_trials=1, rjs_max_rounds=1))
runs = {}
for devices in (1, 2):
    sched = eng.scheduler(num_steps=9, key=key, slots=16, epoch_len=3,
                          capacity=40, devices=devices)
    pending = list(range(40))
    while pending or sched.busy:
        n = sched.free_slots().size
        take, pending = pending[:n], pending[n:]
        if take:
            sched.admit(take, np.asarray(take, np.int32) % 200)
        sched.run_epoch()
    runs[devices] = sched
one, two = runs[1], runs[2]
np.testing.assert_array_equal(one.paths, two.paths)
for k in one.totals:
    if k not in ("ervs_lane_trips", "dist_searches"):
        assert one.totals[k] == two.totals[k], (k, one.totals, two.totals)
assert 0 < two.totals["ervs_lane_trips"] < two.totals["ervs_trips"] * 16
# the dist searches follow the lane-trips; the eRJS trials' stay equal
assert len({s.totals["dist_searches"] - s.totals["ervs_lane_trips"] * 64
            for s in (one, two)}) == 1
hlo = eng._epoch_fn.lower(
    two.state, two.tables, two.graph_view, two.stats_view, epoch_len=3,
    num_steps=9, pad=two.pad_view, max_tiles=two.max_tiles_view,
    shards=2).compile().as_text()
moved = re.findall(r"\s(all-gather|all-to-all|collective-permute)\S*\(", hlo)
assert not moved, moved
assert re.search(r"\sall-reduce\S*\(", hlo)  # the scalar sums and maxes

# spec machinery on a real 2-device mesh: slot dims shard, indivisible
# pools fall back to replication instead of mis-sharding
mesh = walker_mesh(2)
assert walker_spec(jnp.zeros((4, 3)), 4, mesh) == P("walkers", None)
assert walker_spec(jnp.zeros((3, 4)), 3, mesh) == P(None, None)
assert walker_spec(jnp.zeros((7,)), 4, mesh) == P()
assert walker_spec(jnp.float32(0), 4, mesh) == P()
print("MULTIDEVICE-OK")
"""


def test_two_device_scheduler_bit_identical():
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src",
             # the child forces its own device count
             "XLA_FLAGS": ""})
    assert "MULTIDEVICE-OK" in out.stdout, out.stderr[-2000:]


_PROGRAM_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import numpy as np
from repro.core import EngineConfig, WalkEngine
from repro.graphs import random_graph
from repro.walks import ppr_nibble, visited_avoiding

assert len(jax.devices()) == 2, jax.devices()
g = random_graph(200, 8, seed=1)
key = jax.random.key(3)
for prog in [visited_avoiding(window=12), ppr_nibble(alpha=0.3, eps=2e-2)]:
    for method in ["ervs", "adaptive"]:
        eng = WalkEngine(g, prog, EngineConfig(method=method, tile=64))
        # 13 queries through 4 slots, 2-step epochs: stateful refills and
        # (for ppr_nibble) should_stop-freed slots handed to new queries,
        # sharded over 2 devices — must stay bit-identical throughout.
        one = eng.run(np.arange(13), num_steps=9, key=key,
                      batch=4, epoch_len=2, devices=1)
        two = eng.run(np.arange(13), num_steps=9, key=key,
                      batch=4, epoch_len=2, devices=2)
        full = eng.run(np.arange(13), num_steps=9, key=key)
        tag = f"{prog.name}/{method}"
        np.testing.assert_array_equal(one.paths, two.paths, err_msg=tag)
        np.testing.assert_array_equal(full.paths, two.paths, err_msg=tag)
        assert one.frac_rjs == two.frac_rjs == full.frac_rjs, tag
        assert one.frac_precomp == two.frac_precomp == full.frac_precomp, tag
        assert one.live_steps == two.live_steps == full.live_steps, tag
        assert one.rjs_fallbacks == two.rjs_fallbacks, tag
        # stopped/dead walkers never count: every live step emitted a node
        # or was a dead-end attempt (at most one per query)
        emitted = int((two.paths[:, 1:] >= 0).sum())
        assert emitted <= two.live_steps <= emitted + 13, tag
print("PROGRAMS-MULTIDEVICE-OK")
"""


def test_two_device_walk_programs_bit_identical():
    """WalkProgram state (wstate refills) and should_stop slot-freeing
    under the forced 2-device mesh: paths and live-lane telemetry must be
    bit-identical to single-device execution."""
    out = subprocess.run(
        [sys.executable, "-c", _PROGRAM_CHILD], capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": "src", "XLA_FLAGS": ""})
    assert "PROGRAMS-MULTIDEVICE-OK" in out.stdout, out.stderr[-2000:]


class TestShardedSchedulerArgs:
    """Validation paths that hold on any host (no forced devices)."""

    def _engine(self):
        g = random_graph(60, 6, seed=0)
        return WalkEngine(g, deepwalk(), EngineConfig(method="ervs", tile=64))

    def test_run_rejects_nonpositive_devices(self):
        eng = self._engine()
        with pytest.raises(ValueError, match="devices"):
            eng.run(np.arange(4), num_steps=3, devices=0)

    def test_mesh_rejects_more_devices_than_available(self):
        with pytest.raises(ValueError, match="num_devices"):
            walker_mesh(len(jax.devices()) + 1)

    def test_walk_batch_rejects_indivisible_batch(self):
        eng = self._engine()
        with pytest.raises(ValueError, match="divide"):
            eng.walk_batch(np.arange(7, dtype=np.int32), jax.random.key(0),
                           3, devices=2)

    def test_devices_one_is_the_plain_scheduler(self):
        eng = self._engine()
        a = eng.run(np.arange(6), num_steps=4, key=jax.random.key(1))
        b = eng.run(np.arange(6), num_steps=4, key=jax.random.key(1),
                    devices=1)
        np.testing.assert_array_equal(a.paths, b.paths)
        assert b.per_device is None

    def test_replicated_views_placed_once_per_view(self):
        """walk_batch/scheduler placement is cached per mesh: an unchanged
        view is not broadcast again, a view swapped by a mutation is."""
        eng = self._engine()
        first = eng.replicated_views(walker_mesh(1))
        again = eng.replicated_views(walker_mesh(1))
        assert all(a is b for a, b in zip(first, again))
        eng.apply_updates(inserts=([0], [1], np.float32([2.5])))
        tables, graph, stats = eng.replicated_views(walker_mesh(1))
        assert graph is not first[1] and stats is not first[2]
        v = jnp.arange(60, dtype=jnp.int32)
        np.testing.assert_array_equal(np.asarray(graph.row_degs(v)),
                                      np.asarray(eng.graph.row_degs(v)))

    def test_walker_spec_single_device_mesh(self):
        mesh = walker_mesh(1)
        from jax.sharding import PartitionSpec as P
        assert walker_spec(jnp.zeros((4, 2)), 4, mesh) == P("walkers", None)
        assert walker_spec(jnp.zeros((2, 4)), 4, mesh) == P()
