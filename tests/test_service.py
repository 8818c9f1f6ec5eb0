"""Walk-as-a-service harness: deterministic simulated-clock trace tests.

The serving loop (repro/serving/walk_service.py) must be *provably* the
batch engine wearing a queue: every served path bit-identical to the
equivalent offline ``WalkEngine.run``, every counter conserved after
every scripted event, every admission decision replayable.  A
:class:`~repro.serving.SimClock` plus pinned seeds make whole traces —
bursts, overload, deadline storms, mid-serve graph mutation — exact
replays, so these tests assert equality, not tolerances.

Layers under test here:
* ``serving.stats``      — exact percentiles vs numpy on edge cases
* ``AdmissionQueue``     — priority/FIFO/aging/expiry ordering, plus
                           hypothesis property tests over random
                           admit/complete/expire interleavings
* ``WalkService``        — bit-identity vs offline runs, counter
                           conservation, deadline + rejection semantics
* ``launch.serve_walks`` — the CLI sustains a scripted overload trace
                           without deadlock and reports the SLO counters
"""
import dataclasses
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from _hypothesis_compat import given, settings, st
from repro.core import EngineConfig, WalkEngine
from repro.graphs import random_graph
from repro.launch import serve_walks
from repro.serving import (REJECT_DEADLINE, REJECT_QUEUE_FULL,
                           REJECT_UNKNOWN_PROGRAM, AdmissionQueue,
                           LatencyWindow, ServiceConfig, SimClock,
                           WalkQuery, WalkService, percentile)
from repro.walks import make_workload

STEPS = 6
KEYSEED = 2


@pytest.fixture(scope="module")
def graph():
    return random_graph(60, 6, weight_dist="uniform", seed=3)


def make_service(graph, clock, *, slots=4, epoch_len=2, max_pending=1024,
                 min_service_time=0.0, aging_interval=0.0,
                 method="ervs", rebuild_budget=0, programs=None,
                 fairness="drr", quantum=None, weights=None):
    return WalkService(
        graph,
        ServiceConfig(slots=slots, epoch_len=epoch_len, num_steps=STEPS,
                      max_pending=max_pending, aging_interval=aging_interval,
                      min_service_time=min_service_time, seed=KEYSEED,
                      fairness=fairness, quantum=quantum, weights=weights),
        EngineConfig(method=method, tile=32, rebuild_budget=rebuild_budget),
        programs=programs, clock=clock)


def offline_paths(graph, program_name, starts, *, method="ervs",
                  batch=None, epoch_len=None):
    """The ground truth: a plain batch run over the same queries."""
    eng = WalkEngine(graph, make_workload(program_name),
                     EngineConfig(method=method, tile=32))
    res = eng.run(np.asarray(starts), num_steps=STEPS,
                  key=jax.random.key(KEYSEED), batch=batch,
                  epoch_len=epoch_len)
    return res.paths


def check_conserved(svc):
    st_ = svc.stats()
    assert st_.conserves(), st_
    assert st_.occupancy <= st_.slots
    return st_


# --------------------------------------------------------------------------
# serving.stats — exact percentiles (satellite 3)
# --------------------------------------------------------------------------
class TestLatencyStats:
    def test_empty_window_is_nan(self):
        w = LatencyWindow(8)
        assert math.isnan(w.p50) and math.isnan(w.p99)
        assert math.isnan(percentile([], 50.0))

    def test_single_sample_is_every_percentile(self):
        w = LatencyWindow(8)
        w.add(3.25)
        for q in (0.0, 1.0, 50.0, 99.0, 100.0):
            assert w.percentile(q) == 3.25

    def test_ties_match_numpy(self):
        vals = [2.0, 2.0, 2.0, 5.0, 5.0, 1.0, 1.0]
        for q in (0, 10, 25, 50, 75, 90, 99, 100):
            assert percentile(vals, q) == pytest.approx(
                float(np.percentile(vals, q)), abs=0, rel=0)

    def test_random_windows_match_numpy_exactly(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 7, 64, 257):
            vals = rng.normal(size=n)
            for q in (0, 13.7, 50, 86.5, 99, 100):
                assert percentile(vals, q) == float(np.percentile(vals, q))

    def test_ring_wraparound_keeps_most_recent(self):
        w = LatencyWindow(4)
        for v in range(10):
            w.add(float(v))
        assert len(w) == 4 and w.total == 10
        assert list(w.values()) == [6.0, 7.0, 8.0, 9.0]
        assert w.p50 == float(np.percentile([6, 7, 8, 9], 50))

    def test_validation(self):
        with pytest.raises(ValueError):
            LatencyWindow(0)
        with pytest.raises(ValueError):
            percentile([1.0], 101.0)
        with pytest.raises(ValueError):
            percentile([1.0], -0.5)


# --------------------------------------------------------------------------
# AdmissionQueue — ordering semantics
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Item:
    priority: int
    submit_time: float
    deadline: float = None
    tag: int = 0


class TestAdmissionQueue:
    def test_fifo_within_priority(self):
        q = AdmissionQueue()
        for i in range(6):
            q.push(Item(priority=1, submit_time=0.0, tag=i))
        out = q.pop_batch(6, now=0.0)
        assert [it.tag for it in out] == [0, 1, 2, 3, 4, 5]

    def test_priority_order_then_fifo(self):
        q = AdmissionQueue()
        for i, p in enumerate([0, 2, 1, 2, 0, 1]):
            q.push(Item(priority=p, submit_time=0.0, tag=i))
        out = q.pop_batch(6, now=0.0)
        assert [it.tag for it in out] == [1, 3, 2, 5, 0, 4]

    def test_bounded_push(self):
        q = AdmissionQueue(max_pending=2)
        assert q.push(Item(0, 0.0)) and q.push(Item(0, 0.0))
        assert not q.push(Item(9, 0.0))  # full rejects even high priority
        assert len(q) == 2

    def test_expire_removes_only_lapsed(self):
        q = AdmissionQueue()
        q.push(Item(0, 0.0, deadline=1.0, tag=0))
        q.push(Item(0, 0.0, deadline=5.0, tag=1))
        q.push(Item(0, 0.0, deadline=None, tag=2))
        gone = q.expire(now=2.0)
        assert [it.tag for it in gone] == [0]
        assert [it.tag for it in q.items()] == [1, 2]

    def test_aging_promotes_the_starved(self):
        """A waiting priority-0 item outranks fresh priority-2 arrivals
        once it has aged past (2 - 0) * aging_interval."""
        q = AdmissionQueue(aging_interval=1.0)
        q.push(Item(priority=0, submit_time=0.0, tag=99))
        # a high-priority arrival while the victim is still young wins…
        q.push(Item(priority=2, submit_time=1.0, tag=0))
        assert q.pop_batch(1, now=1.0)[0].tag == 0  # eff 2 beats eff 1
        # …but once the victim ages to the arrival's level, its earlier
        # sequence number breaks the tie: the next fresh burst loses
        q.push(Item(priority=2, submit_time=2.5, tag=1))
        assert q.pop_batch(1, now=2.5)[0].tag == 99

    def test_no_starvation_under_sustained_load(self):
        """Under an endless stream of fresh max-priority arrivals, every
        item is served within (P - p) * aging_interval of queue wait."""
        q = AdmissionQueue(aging_interval=0.5)
        q.push(Item(priority=0, submit_time=0.0, tag=-1))
        now, served_victim = 0.0, None
        for round_ in range(20):
            now = round_ * 0.25
            q.push(Item(priority=3, submit_time=now, tag=round_))
            got = q.pop_batch(1, now=now)[0]
            if got.tag == -1:
                served_victim = now
                break
        assert served_victim is not None
        assert served_victim - 0.0 <= (3 - 0) * 0.5 + 0.25

    def test_validation(self):
        with pytest.raises(ValueError):
            AdmissionQueue(max_pending=-1)
        with pytest.raises(ValueError):
            AdmissionQueue(aging_interval=-0.1)


# --------------------------------------------------------------------------
# AdmissionQueue — hypothesis property tests over random interleavings
# --------------------------------------------------------------------------
OPS = st.lists(
    st.one_of(
        st.tuples(st.just("push"), st.integers(0, 3),
                  st.one_of(st.none(), st.floats(0.1, 3.0))),
        st.tuples(st.just("pop"), st.integers(1, 4)),
        st.tuples(st.just("expire")),
        st.tuples(st.just("tick"), st.floats(0.1, 1.0)),
    ),
    min_size=1, max_size=40,
)


class TestAdmissionQueueProperties:
    @given(ops=OPS, aging=st.sampled_from([0.0, 0.5, 1.0]))
    @settings(max_examples=80, deadline=None)
    def test_interleavings_conserve_and_order(self, ops, aging):
        """Random admit/complete/expire interleavings: no item is ever
        lost or duplicated, expiry only removes lapsed deadlines, and
        pops come out FIFO within each base priority level."""
        q = AdmissionQueue(aging_interval=aging)
        now, tag = 0.0, 0
        pushed, popped, expired = [], [], []
        for op in ops:
            if op[0] == "push":
                it = Item(priority=op[1], submit_time=now,
                          deadline=None if op[2] is None else now + op[2],
                          tag=tag)
                tag += 1
                assert q.push(it)
                pushed.append(it)
            elif op[0] == "pop":
                out = q.pop_batch(op[1], now=now)
                assert len(out) <= op[1]
                popped.extend(out)
            elif op[0] == "expire":
                gone = q.expire(now=now)
                for it in gone:
                    assert it.deadline is not None and it.deadline <= now
                expired.extend(gone)
            else:
                now += op[1]
            # conservation after EVERY event
            assert len(pushed) == len(popped) + len(expired) + len(q)
            assert len({it.tag for it in popped}) == len(popped)
        # FIFO within each base priority: among same-priority items the
        # pop sequence follows arrival order (aging moves levels in
        # lockstep, so it can never reorder equals)
        for p in range(4):
            tags = [it.tag for it in popped if it.priority == p]
            assert tags == sorted(tags)

    @given(ops=OPS)
    @settings(max_examples=40, deadline=None)
    def test_bounded_queue_never_overfills(self, ops):
        q = AdmissionQueue(max_pending=3)
        now = 0.0
        for op in ops:
            if op[0] == "push":
                room = len(q) < 3
                ok = q.push(Item(priority=op[1], submit_time=now))
                assert ok == room and len(q) <= 3
            elif op[0] == "pop":
                q.pop_batch(op[1], now=now)
            elif op[0] == "tick":
                now += op[1]
            assert len(q) <= 3


# --------------------------------------------------------------------------
# WalkService — bit-identity vs offline runs (the headline assertion)
# --------------------------------------------------------------------------
class TestServiceBitIdentity:
    def drive(self, svc, clock, arrivals, tick=0.01):
        """Replay a scripted trace: (time, WalkQuery) pairs on a sim
        clock, conservation checked after every single event."""
        receipts, served, i = [], [], 0
        arrivals = sorted(arrivals, key=lambda a: a[0])
        while i < len(arrivals) or not svc.idle:
            while i < len(arrivals) and arrivals[i][0] <= clock():
                receipts.append(svc.submit(arrivals[i][1]))
                check_conserved(svc)
                i += 1
            served.extend(svc.step())
            check_conserved(svc)
            clock.advance(tick)
        return receipts, served

    def test_steady_trace_matches_offline_run(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock)
        starts = np.arange(11) % graph.num_nodes
        arrivals = [(i * 0.015, WalkQuery(start=int(s), program="deepwalk"))
                    for i, s in enumerate(starts)]
        receipts, served = self.drive(svc, clock, arrivals)
        assert all(r.accepted for r in receipts)
        ref = offline_paths(graph, "deepwalk", starts)
        by_ticket = {s.ticket: s for s in served}
        for i, r in enumerate(receipts):
            np.testing.assert_array_equal(by_ticket[r.ticket].path, ref[i])

    def test_burst_with_priorities_still_matches_submission_order(
            self, graph):
        """Priorities reorder *admission*, never results: RNG streams key
        off the submission-order query id, so row i of the offline run
        matches the i-th submitted query no matter when it got a slot."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=3)
        rng = np.random.default_rng(7)
        starts = rng.integers(0, graph.num_nodes, size=10)
        arrivals = [(0.0, WalkQuery(start=int(s), program="deepwalk",
                                    priority=int(rng.integers(0, 3))))
                    for s in starts]
        receipts, served = self.drive(svc, clock, arrivals)
        ref = offline_paths(graph, "deepwalk", starts)
        by_ticket = {s.ticket: s for s in served}
        for i, r in enumerate(receipts):
            np.testing.assert_array_equal(by_ticket[r.ticket].path, ref[i])

    def test_results_independent_of_slots_and_epoch_len(self, graph):
        """The serving cadence is invisible in the results: 2 slots ×
        epoch 1 serves bit-identically to 8 slots × epoch 3."""
        starts = np.arange(9) % graph.num_nodes
        outs = []
        for slots, epoch_len in ((2, 1), (8, 3)):
            clock = SimClock()
            svc = make_service(graph, clock, slots=slots,
                               epoch_len=epoch_len)
            arrivals = [(0.0, WalkQuery(start=int(s))) for s in starts]
            receipts, served = self.drive(svc, clock, arrivals)
            by_ticket = {s.ticket: s for s in served}
            outs.append(np.stack([by_ticket[r.ticket].path
                                  for r in receipts]))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_multi_tenant_each_program_matches_its_own_offline_run(
            self, graph):
        """Interleaved node2vec + deepwalk queries: each tenant's paths
        equal a batch run of just that tenant's queries, in per-tenant
        submission order."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=3)
        rng = np.random.default_rng(5)
        progs = ["deepwalk", "node2vec"]
        arrivals, per_prog = [], {p: [] for p in progs}
        for i in range(12):
            p = progs[int(rng.integers(0, 2))]
            s = int(rng.integers(0, graph.num_nodes))
            per_prog[p].append(s)
            arrivals.append((i * 0.01, WalkQuery(start=s, program=p)))
        receipts, served = self.drive(svc, clock, arrivals)
        by_ticket = {s.ticket: s for s in served}
        for p in progs:
            ref = offline_paths(graph, p, per_prog[p])
            got = [by_ticket[r.ticket].path
                   for (_, q), r in zip(arrivals, receipts)
                   if q.program == p]
            np.testing.assert_array_equal(np.stack(got), ref)

    def test_mid_serve_update_graph_matches_before_and_after_runs(
            self, graph):
        """Mid-serve ``update_graph``: queries finished before the swap
        match an offline run on the OLD graph; queries submitted after
        it match an offline run on the NEW graph (with their service
        query ids), while counters keep conserving throughout."""
        from test_rebuild import mutate_row
        clock = SimClock()
        svc = make_service(graph, clock)
        starts = np.arange(12) % graph.num_nodes
        # phase 1: six queries served to completion on the old graph
        r1, s1 = self.drive(svc, clock, [
            (0.0, WalkQuery(start=int(s))) for s in starts[:6]])
        g2 = mutate_row(mutate_row(graph, 3, salt=11), 17, salt=12)
        svc.update_graph(g2, invalidated=[3, 17])
        check_conserved(svc)
        # phase 2: six more, served on the new graph with qids 6..11
        r2, s2 = self.drive(svc, clock, [
            (clock(), WalkQuery(start=int(s))) for s in starts[6:]])
        by_ticket = {s.ticket: s for s in s1 + s2}
        ref_old = offline_paths(graph, "deepwalk", starts[:6])
        for i, r in enumerate(r1):
            np.testing.assert_array_equal(by_ticket[r.ticket].path,
                                          ref_old[i])
        # offline equivalent of phase 2: same streams = qids 6..11, i.e.
        # rows 6..11 of a 12-query batch run on the new graph
        ref_new = offline_paths(g2, "deepwalk", starts)[6:]
        for i, r in enumerate(r2):
            np.testing.assert_array_equal(by_ticket[r.ticket].path,
                                          ref_new[i])

    def test_update_graph_under_in_flight_walkers_is_deterministic(
            self, graph):
        """Walkers crossing the swap epoch (the documented offline
        carve-out) still replay bit-identically: two services driven
        through the same scripted mutation trace agree exactly."""
        from test_rebuild import mutate_row
        g2 = mutate_row(graph, 5, salt=21)

        def run_once():
            clock = SimClock()
            svc = make_service(graph, clock, slots=4, epoch_len=1,
                               method="its_precomp", rebuild_budget=2)
            starts = np.arange(10) % graph.num_nodes
            receipts = [svc.submit(WalkQuery(start=int(s)))
                        for s in starts]
            served = []
            for step in range(200):
                if step == 2:  # mid-serve, walkers still in flight
                    svc.update_graph(g2, invalidated=[5])
                served.extend(svc.step())
                check_conserved(svc)
                clock.advance(0.01)
                if svc.idle:
                    break
            assert svc.idle
            by_ticket = {s.ticket: s for s in served}
            return np.stack([by_ticket[r.ticket].path for r in receipts])

        np.testing.assert_array_equal(run_once(), run_once())


# --------------------------------------------------------------------------
# WalkService — admission control, deadlines, counter conservation
# --------------------------------------------------------------------------
class TestServiceAdmission:
    def test_queue_full_rejects_with_reason(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock, slots=2, max_pending=3)
        receipts = [svc.submit(WalkQuery(start=i)) for i in range(6)]
        assert [r.accepted for r in receipts] == [True] * 3 + [False] * 3
        assert all(r.reason == REJECT_QUEUE_FULL for r in receipts[3:])
        st_ = check_conserved(svc)
        assert st_.rejected_full == 3 and st_.pending == 3
        svc.drain()
        assert check_conserved(svc).completed == 3

    def test_infeasible_deadline_rejected_not_expired(self, graph):
        clock = SimClock(start=10.0)
        svc = make_service(graph, clock, min_service_time=0.5)
        r = svc.submit(WalkQuery(start=0, deadline=10.2))
        assert not r.accepted and r.reason == REJECT_DEADLINE
        r = svc.submit(WalkQuery(start=0, deadline=12.0))
        assert r.accepted
        st_ = check_conserved(svc)
        assert st_.rejected_deadline == 1 and st_.admitted == 1

    def test_unknown_program_rejected_without_building_tenant(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock)
        r = svc.submit(WalkQuery(start=0, program="nope"))
        assert not r.accepted and r.reason == REJECT_UNKNOWN_PROGRAM
        assert "nope" in r.detail
        assert svc._tenants == {}
        assert check_conserved(svc).rejected_unknown == 1

    def test_pending_deadline_expires_in_queue(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock, slots=2)
        # 2 fill the slots; the 3rd waits with a deadline that lapses
        receipts = [svc.submit(WalkQuery(start=i, deadline=None))
                    for i in range(2)]
        receipts.append(svc.submit(WalkQuery(start=2, deadline=0.02)))
        svc.step()
        check_conserved(svc)
        clock.advance(0.05)  # past the pending query's deadline
        served = svc.step()
        expired = [s for s in served if s.status == "expired"]
        assert [e.ticket for e in expired] == [receipts[2].ticket]
        assert expired[0].path is None and math.isnan(expired[0].wait)
        svc.drain()
        st_ = check_conserved(svc)
        assert st_.expired == 1 and st_.completed == 2

    def test_in_flight_deadline_killed_with_partial_path(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock, slots=2, epoch_len=1)
        r = svc.submit(WalkQuery(start=1, deadline=0.025))
        svc.step()  # admitted, walked 1 of 6 steps
        check_conserved(svc)
        assert svc.in_flight == 1
        clock.advance(0.05)
        served = svc.step()
        assert [s.status for s in served] == ["expired"]
        got = served[0]
        assert got.ticket == r.ticket and got.path is not None
        assert 0 < got.steps < STEPS  # a partial walk came back
        assert got.path[0] == 1 and (got.path[got.steps + 1:] == -1).all()
        st_ = check_conserved(svc)
        assert st_.expired == 1 and st_.in_flight == 0
        # the freed slot is reusable: a fresh query completes
        assert svc.submit(WalkQuery(start=0)).accepted
        done = svc.drain()
        assert [s.status for s in done] == ["completed"]
        check_conserved(svc)

    def test_deadline_storm_counters_conserve_after_every_event(
            self, graph):
        """A storm of tight/loose deadlines under overload: after every
        submit and every step the ledger balances and occupancy stays
        within the slot pool."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=3, epoch_len=1,
                           max_pending=6, min_service_time=0.005)
        rng = np.random.default_rng(9)
        for i in range(24):
            dl = clock() + float(rng.choice([0.001, 0.04, 2.0]))
            svc.submit(WalkQuery(start=int(rng.integers(0, 60)),
                                 priority=int(rng.integers(0, 2)),
                                 deadline=dl))
            check_conserved(svc)
            if i % 3 == 2:
                svc.step()
                check_conserved(svc)
                clock.advance(0.015)
        while not svc.idle:
            svc.step()
            check_conserved(svc)
            clock.advance(0.015)
        st_ = check_conserved(svc)
        assert st_.submitted == 24
        assert st_.rejected > 0 and st_.expired > 0 and st_.completed > 0
        assert st_.peak_occupancy <= st_.slots == 3
        assert st_.pending == 0 and st_.in_flight == 0
        # the latency telemetry saw every completed + admitted-expired
        assert math.isfinite(st_.latency_p50)
        assert math.isfinite(st_.queue_wait_p99)

    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=8, deadline=None)
    def test_property_random_interleavings_conserve(self, graph, seed):
        """Hypothesis drives random submit/step/advance/expire
        interleavings against a live service: the slot accounting
        invariant holds after every event, and the service always
        drains to idle (no starvation, no leaked slots)."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=2, epoch_len=1,
                           max_pending=4, aging_interval=0.02)
        rng = np.random.default_rng(seed)
        for _ in range(18):
            op = rng.integers(0, 3)
            if op == 0:
                dl = None if rng.random() < 0.5 else \
                    clock() + float(rng.choice([0.01, 0.5]))
                svc.submit(WalkQuery(start=int(rng.integers(0, 60)),
                                     priority=int(rng.integers(0, 3)),
                                     deadline=dl))
            elif op == 1:
                svc.step()
            else:
                clock.advance(float(rng.choice([0.005, 0.03])))
            check_conserved(svc)
        while not svc.idle:
            svc.step()
            clock.advance(0.01)
            check_conserved(svc)
        st_ = svc.stats()
        assert st_.admitted == st_.completed + st_.expired


# --------------------------------------------------------------------------
# launch.serve_walks — the CLI sustains scripted traces (satellite CLI)
# --------------------------------------------------------------------------
class TestServeWalksCLI:
    def run_cli(self, capsys, monkeypatch, *flags):
        monkeypatch.setattr(sys, "argv", [
            "serve_walks", "--sim-clock", "--nodes", "200",
            "--avg-degree", "6", "--steps", "6", "--slots", "8",
            "--epoch-len", "2", "--graph", "random", *flags])
        serve_walks.main()
        return capsys.readouterr().out

    def test_overload_trace_reports_rejections(self, capsys, monkeypatch):
        out = self.run_cli(capsys, monkeypatch, "--trace", "overload",
                           "--queries", "48", "--seed", "1")
        assert "queue-full" in out and "p99=" in out
        # the overload trace must actually reject (bounded queue) and
        # still finish every admitted query
        assert " 48 submitted -> " in out
        admitted = int(out.split(" submitted -> ")[1].split(" admitted")[0])
        assert admitted < 48

    def test_deadline_storm_trace_reports_expiries(self, capsys,
                                                   monkeypatch):
        out = self.run_cli(capsys, monkeypatch, "--trace",
                           "deadline-storm", "--queries", "24",
                           "--tick", "0.01", "--seed", "2")
        assert "expired" in out
        expired = int(out.split(" completed + ")[1].split(" expired")[0])
        assert expired > 0

    def test_burst_trace_with_mid_serve_mutation(self, capsys,
                                                 monkeypatch):
        out = self.run_cli(capsys, monkeypatch, "--trace", "burst",
                           "--queries", "24", "--interarrival", "0.05",
                           "--mutate-at", "0.06", "--method",
                           "its_precomp", "--seed", "3")
        assert "rebuilt_rows=" in out
        rebuilt = int(out.split("rebuilt_rows=")[1].split()[0])
        assert rebuilt > 0


# --------------------------------------------------------------------------
# Cross-tenant fairness (DRR) + sharded-slot tenants (satellites 1, 3)
# --------------------------------------------------------------------------
class TestFairness:
    """Deficit round robin replaces one-epoch-per-busy-tenant: weighted
    walker-step shares under overload, with the legacy ``epoch`` mode
    kept as a config escape hatch and bit-identical paths either way."""

    WEIGHTS = {"deepwalk": 3.0, "node2vec": 1.0}

    def _flood(self, svc, per_tenant=40, seed=7):
        rng = np.random.default_rng(seed)
        for _ in range(per_tenant):
            for prog in self.WEIGHTS:
                r = svc.submit(WalkQuery(start=int(rng.integers(0, 60)),
                                         program=prog))
                assert r.accepted

    def test_weighted_shares_within_10pct_under_overload(self, graph):
        """Two tenants at 3:1 weights, both backlogged throughout: the
        cumulative walker-step split stays within 10% of 3:1 (ISSUE
        acceptance).  The exact DRR bound is one epoch of overdraft per
        round, so with enough rounds the measured share pins down."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=2, epoch_len=2,
                           weights=self.WEIGHTS)
        self._flood(svc, per_tenant=40)
        for _ in range(12):  # both tenants stay backlogged for all rounds
            svc.step()
            check_conserved(svc)
        st_ = check_conserved(svc)
        steps = {n: t["walker_steps"] for n, t in st_.per_tenant.items()}
        assert st_.pending > 0  # still overloaded: shares were contested
        total = sum(steps.values())
        share = steps["deepwalk"] / total
        assert abs(share - 0.75) <= 0.10 * 0.75, steps
        # per-tenant ledger: epochs and steps sum to the service totals
        assert sum(t["epochs_run"] for t in st_.per_tenant.values()) \
            == st_.epochs
        assert st_.per_tenant["deepwalk"]["weight"] == 3.0
        while not svc.idle:
            svc.step()
        check_conserved(svc)

    def test_equal_weights_split_evenly(self, graph):
        clock = SimClock()
        svc = make_service(graph, clock, slots=2, epoch_len=2)
        self._flood(svc, per_tenant=30)
        for _ in range(10):
            svc.step()
        st_ = check_conserved(svc)
        steps = {n: t["walker_steps"] for n, t in st_.per_tenant.items()}
        assert st_.pending > 0
        share = steps["deepwalk"] / sum(steps.values())
        assert abs(share - 0.5) <= 0.10 * 0.5, steps
        while not svc.idle:
            svc.step()

    def test_paths_identical_across_fairness_modes(self, graph):
        """The determinism contract survives the scheduler swap: drr
        and legacy epoch mode serve bit-identical paths (streams are
        keyed per tenant-local qid, not by service timing)."""
        outs = {}
        for mode in ("drr", "epoch"):
            clock = SimClock()
            svc = make_service(graph, clock, slots=3,
                               fairness=mode, weights=self.WEIGHTS)
            rng = np.random.default_rng(11)
            tickets = []
            for _ in range(14):
                prog = ("deepwalk", "node2vec")[int(rng.integers(0, 2))]
                r = svc.submit(WalkQuery(start=int(rng.integers(0, 60)),
                                         program=prog))
                tickets.append(r.ticket)
            done = {}
            while not svc.idle:
                for w in svc.step():
                    done[w.ticket] = w
                check_conserved(svc)
            outs[mode] = [done[t].path for t in tickets]
        for a, b in zip(outs["drr"], outs["epoch"]):
            np.testing.assert_array_equal(a, b)

    def test_legacy_epoch_mode_matches_offline(self, graph):
        """fairness="epoch" (the pre-DRR loop) still serves paths
        bit-identical to the offline batch run and keeps the ledger."""
        clock = SimClock()
        svc = make_service(graph, clock, slots=4, fairness="epoch")
        starts = list(range(0, 36, 3))
        tickets = [svc.submit(WalkQuery(start=s)).ticket for s in starts]
        done = {}
        while not svc.idle:
            for w in svc.step():
                done[w.ticket] = w
            check_conserved(svc)
        got = np.stack([done[t].path for t in tickets])
        np.testing.assert_array_equal(
            got, offline_paths(graph, "deepwalk", starts))

    def test_config_validation(self, graph):
        with pytest.raises(ValueError):
            make_service(graph, SimClock(), fairness="lottery")
        with pytest.raises(ValueError):
            make_service(graph, SimClock(), quantum=0)
        with pytest.raises(ValueError):
            make_service(graph, SimClock(),
                         weights={"deepwalk": 0.0})


_SHARDED_TENANT_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import numpy as np
from repro.core import EngineConfig, WalkEngine
from repro.graphs import random_graph
from repro.serving import ServiceConfig, SimClock, WalkQuery, WalkService
from repro.walks import make_workload

assert len(jax.devices()) == 2, jax.devices()
g = random_graph(60, 6, weight_dist="uniform", seed=3)
starts = [int(s) for s in np.random.default_rng(0).integers(0, 60, 13)]

def serve(devices):
    svc = WalkService(
        g, ServiceConfig(slots=4, epoch_len=2, num_steps=6, seed=2,
                         devices=devices),
        EngineConfig(method="ervs", tile=32), clock=SimClock())
    tickets = [svc.submit(WalkQuery(start=s)).ticket for s in starts]
    done = {}
    while not svc.idle:
        for w in svc.step():
            done[w.ticket] = w
    st = svc.stats()
    assert st.conserves(), st
    assert st.completed == len(starts)
    return [done[t].path for t in tickets]

one = serve(1)
two = serve(2)
eng = WalkEngine(g, make_workload("deepwalk"),
                 EngineConfig(method="ervs", tile=32))
full = eng.run(np.asarray(starts), num_steps=6,
               key=jax.random.key(2)).paths
for a, b, c in zip(one, two, full):
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
print("SHARDED-TENANT-OK")
"""


def test_sharded_tenant_bit_identical_to_single_device():
    """ServiceConfig(devices=2) on a forced 2-device host mesh: served
    paths bit-identical to devices=1 and to the offline batch run
    (XLA device-count forcing must precede the jax import, so the mesh
    leg runs in a subprocess — same pattern as test_multidevice.py)."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_TENANT_CHILD], capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": "src",
             # the child forces its own device count
             "XLA_FLAGS": ""})
    assert "SHARDED-TENANT-OK" in out.stdout, out.stderr[-2000:]
