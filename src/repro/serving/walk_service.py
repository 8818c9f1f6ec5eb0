"""Walk-as-a-service: a continuously-batched query serving loop.

``WalkService`` turns the engine's streaming epoch scheduler
(:class:`repro.core.EpochScheduler` — fixed walker slots, host refill
queue, mid-run slot recycling) into a long-lived service: concurrent
clients :meth:`~WalkService.submit` walk queries, the service admits them
into free slots at epoch boundaries without retrace, streams completed
paths back as walkers terminate, and interleaves ``RebuildQueue`` drains
from concurrent :meth:`~WalkService.update_graph` calls.

On top of the scheduler it adds the serving layer a batch engine lacks:

* **Multi-tenancy** — each query carries its own walk-program choice
  (:attr:`WalkQuery.program`, a name resolved against the
  ``repro.walks`` registry or the service's ``programs`` mapping).  Each
  program gets its own engine + slot pool (one jitted epoch per tenant;
  lanes of different programs never share a kernel, so per-tenant
  results stay bit-identical to a batch run).
* **Admission control** — a bounded pending queue with priorities and
  arrival-order fairness (FIFO within priority, optional aging so low
  priorities cannot starve), rejecting with a reason when the queue is
  full or a deadline is infeasible.
* **Cross-tenant fairness** — a deficit-round-robin scheduler
  (:class:`DeficitRoundRobin`) apportions GPU time between tenants in
  *walker-steps* (the ``EpochReport.walker_steps`` charge): each tenant
  accrues ``quantum * weight`` credit per service step and runs epochs
  until its credit is spent, so a hot tenant cannot starve light ones,
  idle quanta roll over (bounded by ``deficit_cap``), and weighted
  walker-step shares converge to the configured ratio under overload.
* **Cancellation** — :meth:`~WalkService.cancel` retires a ticket
  wherever it is: dropped from the pending queue, or killed in its slot
  through the alive-mask machinery with the partial path returned.
* **Deadline enforcement** — pending queries past their deadline expire
  in the queue; in-flight walkers past theirs are killed at the next
  epoch boundary through the scheduler's alive-mask machinery (exactly
  how ``should_stop`` retires a lane), returning the partial path.
* **SLO telemetry** — :class:`ServiceStats`, the service counterpart of
  ``WalkResult``: p50/p99 queue wait and completion latency over ring
  buffers (:mod:`repro.serving.stats`), slot occupancy, and counters
  that conserve — ``admitted == completed + expired + pending +
  in_flight`` after every event.

Determinism contract (what tests/test_service.py pins)
------------------------------------------------------
Random streams are keyed per *tenant-local query id* in submission
order, exactly like a batch run keys them per query index — so every
served path is bit-identical to ``WalkEngine.run`` over the same
queries: the i-th accepted query of a program matches row i of
``run(starts_in_submission_order)`` with the same key, regardless of
arrival pattern, priorities, slot count or epoch cadence.  The clock is
injected (``clock=``), so a simulated clock makes whole traces —
arrivals, deadline storms, overload — exactly replayable.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax
import numpy as np

from repro.core import EngineConfig, WalkEngine
from repro.core.runtime import DEFAULT_EPOCH_LEN
from repro.core.types import StepStats, WalkProgram
from repro.graphs import GraphDelta
from repro.serving.stats import LatencyWindow

# Rejection reason codes (SubmitReceipt.reason)
REJECT_QUEUE_FULL = "queue-full"
REJECT_DEADLINE = "deadline-infeasible"
REJECT_UNKNOWN_PROGRAM = "unknown-program"

# ServedWalk.status values
COMPLETED = "completed"
EXPIRED = "expired"
CANCELLED = "cancelled"

# ServiceConfig.fairness modes
FAIRNESS_MODES = ("drr", "epoch")


class SimClock:
    """Deterministic manually-advanced clock for replayable traces.

    Pass an instance as ``WalkService(clock=...)`` (it is callable like
    ``time.monotonic``); tests and the ``--sim-clock`` CLI mode advance
    it explicitly, so deadline storms and arrival bursts replay exactly.
    """

    def __init__(self, start: float = 0.0):
        self.now = float(start)

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"SimClock cannot run backwards (dt={dt})")
        self.now += float(dt)
        return self.now


@dataclasses.dataclass(frozen=True)
class WalkQuery:
    """One client walk request.

    ``program`` names the walk program (multi-tenant: resolved against
    the service's ``programs`` mapping, then the ``repro.walks``
    registry).  ``deadline`` is an *absolute* service-clock time by which
    the full path must be delivered; ``priority`` orders admission
    (higher first, FIFO within a priority level).
    """

    start: int
    program: str = "deepwalk"
    priority: int = 0
    deadline: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class SubmitReceipt:
    """What ``submit`` returns: the ticket (a service-global query id)
    when accepted, or the rejection reason code + human detail."""

    accepted: bool
    ticket: Optional[int] = None
    reason: Optional[str] = None
    detail: Optional[str] = None


@dataclasses.dataclass
class ServedWalk:
    """One finished query, streamed back from ``step``.

    ``status`` is ``"completed"`` (walked to termination: full length,
    dead end, or the program's own ``should_stop``), ``"expired"``
    (deadline passed) or ``"cancelled"`` (client cancel) — for the
    latter two ``path`` holds the partial walk if the query ever held a
    slot, else ``None``.  ``wait`` is queue time (nan when never
    admitted); ``latency`` is submit → finish.
    """

    ticket: int
    program: str
    status: str
    path: Optional[np.ndarray]
    steps: int
    submit_time: float
    admit_time: Optional[float]
    finish_time: float
    wait: float
    latency: float


@dataclasses.dataclass
class _Ticket:
    """Service-side bookkeeping for one accepted query."""

    ticket: int  # service-global id (client-facing)
    qid: int  # tenant-local query id — picks the RNG stream + path row
    query: WalkQuery
    submit_time: float
    admit_time: Optional[float] = None

    # AdmissionQueue reads these off the queued item:
    @property
    def priority(self) -> int:
        return self.query.priority

    @property
    def deadline(self) -> Optional[float]:
        return self.query.deadline


class AdmissionQueue:
    """Bounded pending queue: priority order, FIFO within a priority,
    optional aging so sustained high-priority load cannot starve anyone.

    Items need ``priority`` / ``deadline`` / ``submit_time`` attributes.
    Effective priority at time ``now`` is ``priority + floor((now -
    submit_time) / aging_interval)`` (aging disabled at 0) — two items
    with the same base priority age in lockstep, so arrival order between
    them is always preserved, while a waiting low-priority item
    eventually outranks any bounded fresh priority: an item of priority
    ``p`` waits at most ``(P - p) * aging_interval`` behind priority-``P``
    arrivals before it wins the tie-break (lower sequence number) too.
    """

    def __init__(self, max_pending: Optional[int] = None,
                 aging_interval: float = 0.0):
        if max_pending is not None and max_pending < 0:
            raise ValueError(
                f"max_pending must be >= 0 or None, got {max_pending}")
        if aging_interval < 0:
            raise ValueError(
                f"aging_interval must be >= 0 (0 disables aging), "
                f"got {aging_interval}")
        self.max_pending = max_pending
        self.aging_interval = float(aging_interval)
        self._items: List[tuple] = []  # (seq, item), seq strictly increasing
        self._seq = 0

    def __len__(self) -> int:
        return len(self._items)

    def items(self) -> list:
        """Pending items in arrival order (inspection only)."""
        return [it for _, it in self._items]

    def effective_priority(self, item, now: float) -> int:
        p = int(item.priority)
        if self.aging_interval > 0:
            p += int(max(0.0, now - item.submit_time)
                     // self.aging_interval)
        return p

    def push(self, item) -> bool:
        """Enqueue; False when the queue is at ``max_pending``."""
        if (self.max_pending is not None
                and len(self._items) >= self.max_pending):
            return False
        self._items.append((self._seq, item))
        self._seq += 1
        return True

    def pop_batch(self, k: int, now: float) -> list:
        """The next ``k`` items to admit: highest effective priority
        first, sequence number (arrival order) breaking ties."""
        if k <= 0 or not self._items:
            return []
        order = sorted(
            range(len(self._items)),
            key=lambda i: (-self.effective_priority(self._items[i][1], now),
                           self._items[i][0]))
        chosen = order[:k]
        batch = [self._items[i][1] for i in chosen]
        drop = set(chosen)
        self._items = [x for i, x in enumerate(self._items)
                       if i not in drop]
        return batch

    def remove(self, item) -> bool:
        """Drop one queued item by identity (cancellation); False when
        the item is not pending here."""
        for i, (_, it) in enumerate(self._items):
            if it is item:
                del self._items[i]
                return True
        return False

    def expire(self, now: float) -> list:
        """Remove and return every pending item whose deadline passed."""
        out = [it for _, it in self._items
               if it.deadline is not None and it.deadline <= now]
        if out:
            self._items = [(s, it) for s, it in self._items
                           if not (it.deadline is not None
                                   and it.deadline <= now)]
        return out


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the serving loop (the ``EngineConfig`` counterpart)."""

    #: walker slots per tenant (one slot pool per walk program)
    slots: int = 64
    #: scan steps between epoch boundaries (admission/expiry/streaming
    #: all happen at boundaries); None → the engine default cadence
    epoch_len: Optional[int] = 8
    #: walk length served per query; None → each program's ``walk_len``
    num_steps: Optional[int] = None
    #: total pending queries across tenants before queue-full rejection
    max_pending: int = 1024
    #: seconds of queue wait per +1 effective priority (0 disables
    #: aging; see AdmissionQueue — bounds starvation under load)
    aging_interval: float = 0.0
    #: a deadline closer than this to now is rejected as infeasible
    #: instead of admitted-then-expired
    min_service_time: float = 0.0
    #: ring-buffer capacity of the p50/p99 latency windows
    latency_window: int = 2048
    #: per-tenant run key seed (stream i of a tenant = fold_in(key(seed), i))
    seed: int = 0
    #: cross-tenant scheduling: "drr" (deficit round robin in
    #: walker-steps — see DeficitRoundRobin) or "epoch" (the legacy one-
    #: epoch-per-busy-tenant round robin, load-blind)
    fairness: str = "drr"
    #: DRR credit accrued per tenant per service step, in walker-steps;
    #: None → slots * epoch_len (one fully-occupied epoch's worth)
    quantum: Optional[int] = None
    #: idle quanta roll over up to deficit_cap * quantum * weight
    deficit_cap: float = 4.0
    #: per-tenant walker-step weight by program name (unlisted → 1.0)
    weights: Optional[Mapping[str, float]] = None
    #: shard every tenant's slot pool over this many local devices
    #: (scheduler(devices=N); results stay bit-identical to devices=1)
    devices: int = 1

    def __post_init__(self):
        if self.slots <= 0:
            raise ValueError(f"slots must be positive, got {self.slots}")
        if self.epoch_len is not None and self.epoch_len <= 0:
            raise ValueError(
                f"epoch_len must be positive or None, got {self.epoch_len}")
        if self.num_steps is not None and self.num_steps <= 0:
            raise ValueError(
                f"num_steps must be positive or None, got {self.num_steps}")
        if self.max_pending < 0:
            raise ValueError(
                f"max_pending must be >= 0, got {self.max_pending}")
        if self.aging_interval < 0:
            raise ValueError(
                f"aging_interval must be >= 0, got {self.aging_interval}")
        if self.min_service_time < 0:
            raise ValueError(
                f"min_service_time must be >= 0, "
                f"got {self.min_service_time}")
        if self.fairness not in FAIRNESS_MODES:
            raise ValueError(
                f"fairness must be one of {FAIRNESS_MODES}, "
                f"got {self.fairness!r}")
        if self.quantum is not None and self.quantum <= 0:
            raise ValueError(
                f"quantum must be positive or None, got {self.quantum}")
        if self.deficit_cap < 1:
            raise ValueError(
                f"deficit_cap must be >= 1, got {self.deficit_cap}")
        if self.devices <= 0:
            raise ValueError(
                f"devices must be positive, got {self.devices}")
        for name, w in dict(self.weights or {}).items():
            if w <= 0:
                raise ValueError(
                    f"tenant weight must be positive, got {name}={w}")


class DeficitRoundRobin:
    """Cross-tenant deficit-round-robin credit ledger, in walker-steps.

    Classic DRR (Shreedhar & Varghese) with the epoch as the service
    unit and ``EpochReport.walker_steps`` — live walker-steps actually
    executed — as the cost: per round every *busy* tenant accrues
    ``quantum * weight`` credit (capped at ``cap`` rounds' worth, so
    idle quanta roll over but cannot bank unboundedly), and a tenant
    runs epochs while its deficit stays positive, each epoch charged at
    its true live cost.  A deficit may go negative by at most one
    epoch's cost, which is what bounds any tenant's overdraft — hence
    long-run walker-step shares converge to the weight ratio whenever
    demand saturates, and no busy tenant waits more than
    ``ceil(max_epoch_cost / (quantum * weight))`` rounds for service.

    The ledger is pure host arithmetic (no clock, no RNG) so schedules
    are exactly replayable; tests/test_transport.py property-tests work
    conservation, weighted shares, and the starvation bound over random
    cost sequences.
    """

    def __init__(self, quantum: int, cap: float = 4.0):
        if quantum <= 0:
            raise ValueError(f"quantum must be positive, got {quantum}")
        if cap < 1:
            raise ValueError(f"cap must be >= 1, got {cap}")
        self.quantum = int(quantum)
        self.cap = float(cap)
        self._weight: Dict[str, float] = {}
        self._deficit: Dict[str, float] = {}
        self._charged: Dict[str, int] = {}

    def register(self, name: str, weight: float = 1.0) -> None:
        if weight <= 0:
            raise ValueError(
                f"tenant weight must be positive, got {name}={weight}")
        if name not in self._weight:
            self._weight[name] = float(weight)
            self._deficit[name] = 0.0
            self._charged[name] = 0

    def weight(self, name: str) -> float:
        return self._weight[name]

    def deficit(self, name: str) -> float:
        return self._deficit[name]

    def charged(self, name: str) -> int:
        """Total walker-steps ever charged to ``name``."""
        return self._charged[name]

    def begin_round(self, active) -> None:
        """Accrue one quantum (weight-scaled, cap-bounded) for every
        busy tenant; tenants with nothing to run accrue nothing, so an
        idle tenant never banks credit against future arrivals beyond
        the rollover cap."""
        for name in active:
            q = self.quantum * self._weight[name]
            self._deficit[name] = min(self._deficit[name] + q,
                                      q * self.cap)

    def runnable(self, name: str) -> bool:
        return self._deficit[name] > 0.0

    def charge(self, name: str, cost: int) -> None:
        if cost < 0:
            raise ValueError(f"cost must be >= 0, got {cost}")
        self._deficit[name] -= float(cost)
        self._charged[name] += int(cost)

    def pick(self, active) -> str:
        """Work-conservation backstop: when no busy tenant is runnable
        (all deficits spent), the device must not idle — serve the
        least-overdrawn tenant (max deficit; first in ``active`` order
        on ties, so the choice is deterministic)."""
        return max(active, key=lambda n: self._deficit[n])


@dataclasses.dataclass(frozen=True)
class ServiceStats:
    """Snapshot of the service counters — the ``WalkResult`` of serving.

    Counter conservation (asserted by tests after every scripted event):
    ``submitted == admitted + rejected`` and ``admitted == completed +
    expired + cancelled + pending + in_flight`` — a query is always in
    exactly one place.  ``occupancy`` never exceeds ``slots``.
    ``per_tenant`` attributes epochs and walker-steps to each tenant
    (plus its DRR weight and current deficit); the per-tenant sums must
    equal the service-wide ``epochs`` / ``live_steps`` totals, and
    ``conserves()`` checks that too.
    """

    submitted: int
    admitted: int
    rejected_full: int
    rejected_deadline: int
    rejected_unknown: int
    completed: int
    expired: int
    cancelled: int
    pending: int
    in_flight: int
    epochs: int
    slots: int
    occupancy: int
    peak_occupancy: int
    live_steps: int
    frac_rjs: float
    frac_precomp: float
    frac_stale: float
    rebuilt_rows: int
    queue_wait_p50: float
    queue_wait_p99: float
    latency_p50: float
    latency_p99: float
    #: tenant name -> {"epochs_run", "walker_steps", "weight", "deficit"}
    per_tenant: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)

    @property
    def rejected(self) -> int:
        return (self.rejected_full + self.rejected_deadline
                + self.rejected_unknown)

    def conserves(self) -> bool:
        """The admission ledger balances (see class docstring)."""
        return (self.submitted == self.admitted + self.rejected
                and self.admitted == self.completed + self.expired
                + self.cancelled + self.pending + self.in_flight
                and 0 <= self.occupancy <= max(self.slots, 0)
                # every in-flight query holds exactly one slot
                and self.in_flight == self.occupancy
                # per-tenant attribution sums back to the totals
                and self.epochs == sum(
                    int(pt["epochs_run"]) for pt in self.per_tenant.values())
                and self.live_steps == sum(
                    int(pt["walker_steps"])
                    for pt in self.per_tenant.values()))


class ServiceTenant:
    """One walk program's serving lane group: engine + slot pool +
    pending queue + in-flight ledger.  Created on a program's first
    accepted query."""

    def __init__(self, name: str, program: WalkProgram, graph,
                 engine_config: EngineConfig, config: ServiceConfig):
        self.name = name
        self.program = program
        self.engine = WalkEngine(graph, program, engine_config)
        self.num_steps = int(config.num_steps or program.walk_len)
        self.key = jax.random.key(config.seed)
        # track_tables: the serving loop re-adopts the engine's precomp
        # tables every epoch, so background rebuild repairs (and graph
        # mutations) become visible at epoch granularity — the piecewise-
        # deterministic serving contract (vs. the per-run pinned view a
        # batch WalkEngine.run serves from)
        self.sched = self.engine.scheduler(
            num_steps=self.num_steps, key=self.key, slots=config.slots,
            epoch_len=config.epoch_len, track_tables=True,
            devices=config.devices)
        self.queue = AdmissionQueue(max_pending=None,
                                    aging_interval=config.aging_interval)
        self.next_qid = 0  # tenant-local id = offline run's query index
        self.inflight: Dict[int, _Ticket] = {}
        self.epochs_run = 0  # per-tenant attribution (ServiceStats)


class WalkService:
    """The long-lived serving loop (see module docstring).

    The loop is a synchronous state machine: :meth:`submit` enqueues,
    :meth:`step` runs ONE epoch boundary — expire, admit, execute, and
    stream back whatever finished — and :meth:`drain` steps until idle.
    Drive :meth:`step` from a thread, an event loop, or a test's
    simulated clock; the service itself never sleeps or spawns threads,
    which is what makes scripted traces exactly replayable.
    """

    def __init__(self, graph, config: Optional[ServiceConfig] = None,
                 engine_config: Optional[EngineConfig] = None,
                 programs: Optional[Dict[str, WalkProgram]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.graph = graph
        self.config = config or ServiceConfig()
        self.engine_config = engine_config or EngineConfig()
        self.clock = clock
        self._programs = dict(programs or {})
        self._tenants: Dict[str, ServiceTenant] = {}
        self._next_ticket = 0
        self._epochs = 0
        self._peak_occupancy = 0
        self._c = {"submitted": 0, "admitted": 0, "rejected_full": 0,
                   "rejected_deadline": 0, "rejected_unknown": 0,
                   "completed": 0, "expired": 0, "cancelled": 0}
        self._wait_window = LatencyWindow(self.config.latency_window)
        self._latency_window = LatencyWindow(self.config.latency_window)
        # live ticket index (popped on completion/expiry/cancel) — what
        # lets cancel() find a query wherever it currently is
        self._tickets: Dict[int, Tuple[str, _Ticket]] = {}
        quantum = int(self.config.quantum
                      or self.config.slots * (self.config.epoch_len
                                              or DEFAULT_EPOCH_LEN))
        self._drr = DeficitRoundRobin(quantum=quantum,
                                      cap=self.config.deficit_cap)

    # ------------------------------------------------------------ tenants
    def _resolve_program(self, name: str) -> Optional[WalkProgram]:
        if name in self._programs:
            return self._programs[name]
        from repro.walks import WORKLOADS, make_workload
        if name in WORKLOADS:
            return make_workload(name)
        return None

    def tenant(self, name: str) -> ServiceTenant:
        """The lane group serving ``name``, created on first use.
        Raises KeyError for a program neither registered nor supplied."""
        t = self._tenants.get(name)
        if t is None:
            program = self._resolve_program(name)
            if program is None:
                from repro.walks import WORKLOADS
                raise KeyError(
                    f"{name!r} names no walk program; known: "
                    f"{sorted(set(WORKLOADS) | set(self._programs))}")
            t = ServiceTenant(name, program, self.graph,
                              self.engine_config, self.config)
            self._tenants[name] = t
            self._drr.register(
                name, dict(self.config.weights or {}).get(name, 1.0))
        return t

    @property
    def pending(self) -> int:
        return sum(len(t.queue) for t in self._tenants.values())

    @property
    def in_flight(self) -> int:
        return sum(len(t.inflight) for t in self._tenants.values())

    @property
    def idle(self) -> bool:
        return self.pending == 0 and self.in_flight == 0

    # ------------------------------------------------------------- submit
    def submit(self, query: WalkQuery) -> SubmitReceipt:
        """Admission control: accept into the pending queue (returning
        the ticket) or reject with a reason — the queue is full, the
        deadline is infeasible, or the program is unknown.  Rejection
        never builds a tenant, so a typo cannot cost an engine trace."""
        now = self.clock()
        self._c["submitted"] += 1
        if (query.program not in self._tenants
                and self._resolve_program(query.program) is None):
            self._c["rejected_unknown"] += 1
            return SubmitReceipt(
                accepted=False, reason=REJECT_UNKNOWN_PROGRAM,
                detail=f"{query.program!r} names no walk program")
        if (query.deadline is not None
                and query.deadline - now <= self.config.min_service_time):
            self._c["rejected_deadline"] += 1
            return SubmitReceipt(
                accepted=False, reason=REJECT_DEADLINE,
                detail=f"deadline {query.deadline:.3f} within "
                       f"min_service_time of now={now:.3f}")
        if self.pending >= self.config.max_pending:
            self._c["rejected_full"] += 1
            return SubmitReceipt(
                accepted=False, reason=REJECT_QUEUE_FULL,
                detail=f"{self.pending} pending >= max_pending="
                       f"{self.config.max_pending}")
        tenant = self.tenant(query.program)
        ticket = self._next_ticket
        self._next_ticket += 1
        t = _Ticket(ticket=ticket, qid=tenant.next_qid, query=query,
                    submit_time=now)
        tenant.next_qid += 1
        tenant.queue.push(t)  # per-tenant queue is unbounded; the
        self._c["admitted"] += 1  # service-level max_pending bound held
        self._tickets[ticket] = (tenant.name, t)
        return SubmitReceipt(accepted=True, ticket=ticket)

    def cancel(self, ticket: int) -> Optional[ServedWalk]:
        """Retire an accepted query by ticket, wherever it is: dropped
        from the pending queue (``path=None``), or killed in its slot
        through the scheduler's alive-mask machinery with the partial
        path harvested so far.  Returns the terminal ``ServedWalk``
        (status ``"cancelled"``), or None when the ticket is unknown or
        already finished — cancellation never races a delivered result."""
        owner = self._tickets.get(int(ticket))
        if owner is None:
            return None
        now = self.clock()
        name, t = owner
        tenant = self._tenants[name]
        if tenant.queue.remove(t):
            walk = self._finish_walk(t, tenant, now, admitted=False,
                                     status=CANCELLED)
        elif t.qid in tenant.inflight:
            tenant.sched.kill([t.qid])
            del tenant.inflight[t.qid]
            walk = self._finish_walk(t, tenant, now, admitted=True,
                                     status=CANCELLED)
        else:  # pragma: no cover — _tickets is popped on every finish
            return None
        del self._tickets[t.ticket]
        self._c["cancelled"] += 1
        return walk

    # --------------------------------------------------------------- loop
    def _finish_walk(self, t: _Ticket, tenant: ServiceTenant,
                     now: float, admitted: bool, status: str) -> ServedWalk:
        """Terminal ServedWalk for a query that did NOT walk to
        completion (expired or cancelled): partial path when it ever
        held a slot, else ``path=None``."""
        path = steps = None
        if admitted:
            path = tenant.sched.paths[t.qid].copy()
            steps = int((path[1:] >= 0).sum())
        return ServedWalk(
            ticket=t.ticket, program=tenant.name, status=status,
            path=path, steps=steps or 0, submit_time=t.submit_time,
            admit_time=t.admit_time, finish_time=now,
            wait=(t.admit_time - t.submit_time) if admitted
            else float("nan"),
            latency=now - t.submit_time)

    def _expire_tenant(self, tenant: ServiceTenant, now: float,
                       served: List[ServedWalk]) -> None:
        """Deadline expiry — pending queries never get a slot, and
        in-flight walkers are retired through the scheduler's alive-mask
        machinery (like a should_stop verdict), keeping the partial path
        harvested so far."""
        for t in tenant.queue.expire(now):
            self._c["expired"] += 1
            self._tickets.pop(t.ticket, None)
            served.append(self._finish_walk(t, tenant, now,
                                            admitted=False,
                                            status=EXPIRED))
        late = [qid for qid, t in tenant.inflight.items()
                if t.deadline is not None and t.deadline <= now]
        if late:
            tenant.sched.kill(late)
            for qid in late:
                t = tenant.inflight.pop(qid)
                self._c["expired"] += 1
                self._tickets.pop(t.ticket, None)
                served.append(self._finish_walk(t, tenant, now,
                                                admitted=True,
                                                status=EXPIRED))

    def _admit_tenant(self, tenant: ServiceTenant, now: float) -> None:
        """Epoch-boundary admission into free slots, by effective
        priority (FIFO within priority, aged against starvation)."""
        free = tenant.sched.free_slots()
        if free.size and len(tenant.queue):
            batch = tenant.queue.pop_batch(int(free.size), now)
            tenant.sched.admit([t.qid for t in batch],
                               [t.query.start for t in batch])
            for t in batch:
                t.admit_time = now
                tenant.inflight[t.qid] = t
                self._wait_window.add(now - t.submit_time)

    def _run_tenant_epoch(self, tenant: ServiceTenant,
                          served: List[ServedWalk]):
        """One jitted epoch for ``tenant``; completions stream back
        immediately.  Returns the EpochReport (DRR charges off it)."""
        report = tenant.sched.run_epoch()
        self._epochs += 1
        tenant.epochs_run += 1
        self._peak_occupancy = max(self._peak_occupancy, report.occupied)
        fin = self.clock()
        for qid, steps in zip(report.completed, report.steps_taken):
            t = tenant.inflight.pop(int(qid))
            self._c["completed"] += 1
            self._tickets.pop(t.ticket, None)
            self._latency_window.add(fin - t.submit_time)
            served.append(ServedWalk(
                ticket=t.ticket, program=tenant.name,
                status=COMPLETED,
                path=tenant.sched.paths[int(qid)].copy(),
                steps=int(steps), submit_time=t.submit_time,
                admit_time=t.admit_time, finish_time=fin,
                wait=t.admit_time - t.submit_time,
                latency=fin - t.submit_time))
        return report

    def step(self) -> List[ServedWalk]:
        """Run one service step across every active tenant: expire
        lapsed deadlines (pending AND in-flight), admit from the queues
        into free slots, then apportion epochs by the configured
        fairness mode and return every query that finished — completed
        walkers stream out the epoch they terminate.

        Under ``fairness="drr"`` (the default) each busy tenant accrues
        one weighted quantum of walker-step credit and runs epochs until
        it is spent (re-admitting from its queue as slots free), so a
        backlogged tenant gets GPU time proportional to its weight —
        not to how often it happens to be busy.  ``fairness="epoch"``
        is the legacy one-epoch-per-busy-tenant round robin.  Both
        modes key random streams per tenant-local query id, so the
        fairness mode can never change a served path — only when it is
        served.
        """
        now = self.clock()
        served: List[ServedWalk] = []
        for tenant in self._tenants.values():
            self._expire_tenant(tenant, now, served)
            self._admit_tenant(tenant, now)
        if self.config.fairness == "epoch":
            for tenant in self._tenants.values():
                if tenant.sched.busy:
                    self._run_tenant_epoch(tenant, served)
            return served
        busy = [t for t in self._tenants.values() if t.sched.busy]
        if not busy:
            return served
        self._drr.begin_round([t.name for t in busy])
        ran = 0
        for tenant in busy:
            while tenant.sched.busy and self._drr.runnable(tenant.name):
                report = self._run_tenant_epoch(tenant, served)
                self._drr.charge(tenant.name, report.walker_steps)
                ran += 1
                # freed slots refill immediately so the next epoch of
                # this quantum runs full
                self._admit_tenant(tenant, now)
        if not ran:
            # Work conservation: every deficit can be overdrawn from the
            # previous round (an epoch's true cost lands after the
            # runnable check).  Never let the device idle while queries
            # wait — serve the least-overdrawn busy tenant.
            tenant = self._tenants[self._drr.pick([t.name for t in busy])]
            report = self._run_tenant_epoch(tenant, served)
            self._drr.charge(tenant.name, report.walker_steps)
        return served

    def drain(self, max_steps: Optional[int] = 100_000
              ) -> List[ServedWalk]:
        """Step until idle (deadlock guard: raises after ``max_steps``).
        Note a pending query whose deadline never passes and whose slots
        never free would spin — that cannot happen, since every admitted
        walker terminates within ``ceil(num_steps / epoch_len)`` epochs."""
        out: List[ServedWalk] = []
        steps = 0
        while not self.idle:
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"drain() still busy after {steps} steps: "
                    f"{self.pending} pending, {self.in_flight} in flight")
            out.extend(self.step())
            steps += 1
        return out

    # ------------------------------------------------------ graph updates
    def update_graph(self, graph, invalidated=()) -> None:
        """Swap mutated edge weights in under live traffic: forwarded to
        every tenant engine (stale precomp rows enter each engine's
        ``RebuildQueue``, drained ``rebuild_budget`` rows per epoch by
        the serving loop — walks in flight keep stepping, falling back
        to the dynamic path on stale rows until the drains catch up).
        Tenants created later serve the new graph from scratch."""
        self.graph = graph
        for tenant in self._tenants.values():
            tenant.engine.update_graph(graph, invalidated)

    def apply_updates(self, inserts=None, deletes=None) -> dict:
        """Apply structural edits — edge inserts/deletes — under live
        traffic (see :meth:`WalkEngine.apply_updates` for the edit
        format and the delta-overlay semantics).

        Every tenant engine overlays the edits and queues its touched
        precomp rows for the amortized background rebuild; walks in
        flight keep stepping (their next epoch re-pins the spliced
        tables and resets the sampler carry, so they read post-edit
        payloads exactly like a fresh engine's walkers).  The service's
        own graph — what tenants created *later* are built from — is
        advanced by folding the same edits into a fresh CSR.  Returns
        ``{tenant name: UpdateReport}`` (the ``""`` key reports the
        service-graph fold)."""
        reports = {}
        delta = GraphDelta(self.graph)
        reports[""] = delta.apply(inserts, deletes)
        self.graph = delta.compact()
        for tenant in self._tenants.values():
            reports[tenant.name] = tenant.engine.apply_updates(
                inserts, deletes)
        return reports

    # ------------------------------------------------------------- stats
    def stats(self) -> ServiceStats:
        """Counter snapshot; ``stats().conserves()`` holds at any point
        between ``submit``/``step`` calls."""
        totals = dict.fromkeys(
            (f.name for f in dataclasses.fields(StepStats)), 0)
        rebuilt = 0
        per_tenant = {}
        for t in self._tenants.values():
            for k in totals:
                totals[k] += t.sched.totals[k]
            rebuilt += t.sched.rebuilt_rows
            per_tenant[t.name] = {
                "epochs_run": t.epochs_run,
                "walker_steps": int(t.sched.totals["live"]),
                "weight": self._drr.weight(t.name),
                "deficit": self._drr.deficit(t.name),
            }
        live = totals["live"]
        return ServiceStats(
            submitted=self._c["submitted"],
            admitted=self._c["admitted"],
            rejected_full=self._c["rejected_full"],
            rejected_deadline=self._c["rejected_deadline"],
            rejected_unknown=self._c["rejected_unknown"],
            completed=self._c["completed"],
            expired=self._c["expired"],
            cancelled=self._c["cancelled"],
            pending=self.pending,
            in_flight=self.in_flight,
            epochs=self._epochs,
            slots=sum(t.sched.W for t in self._tenants.values()),
            occupancy=sum(t.sched.occupancy
                          for t in self._tenants.values()),
            peak_occupancy=self._peak_occupancy,
            live_steps=live,
            frac_rjs=totals["rjs_served"] / max(live, 1),
            frac_precomp=totals["precomp_served"] / max(live, 1),
            frac_stale=totals["stale_served"] / max(live, 1),
            rebuilt_rows=rebuilt,
            queue_wait_p50=self._wait_window.p50,
            queue_wait_p99=self._wait_window.p99,
            latency_p50=self._latency_window.p50,
            latency_p99=self._latency_window.p99,
            per_tenant=per_tenant,
        )
