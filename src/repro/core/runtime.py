"""Flexi-Runtime — the walk engine (paper §4.1, §5.2, §5.3, Fig. 8).

The engine is sampler-agnostic: ``EngineConfig.method`` resolves through
the :mod:`repro.core.samplers` registry to a :class:`~repro.core.samplers.
Sampler` object, and the jitted step loop simply calls
``sampler.select(ctx, state, rng, active=live)`` — there is no per-method
dispatch here.  The paper's runtime adaptation (per-node eRJS/eRVS choice
via the Eq. 11 cost model, with the §7.1 fallback) lives in
``PartitionedSampler``; registering a new strategy by name makes it
runnable end-to-end with no engine edits.

Step loop: the carry is a :class:`~repro.core.types.WalkerState` pytree
(cur/prev/step/alive/rng per slot) advanced by ``lax.scan``.  Each step
folds the walker's step counter into its per-query stream key, masks the
live lanes (alive ∧ degree>0 ∧ step<L), and records
:class:`~repro.core.types.StepStats` telemetry over live lanes only.

Scheduling (§5.3): the GPU global-atomic work queue becomes a *streaming
epoch scheduler* — ``run`` keeps a fixed number of walker slots, executes
the jitted epoch (``epoch_len`` scan steps), and between epochs refills
slots whose walker finished (walked L steps or dead-ended) from a
host-side queue of pending queries.  Empty slots stay ``alive=False``:
they are masked out of every kernel and never touch paths or telemetry,
so query counts that don't divide the slot count cannot skew ``frac_rjs``.
Queries are degree-sorted host-side (degree-similar co-scheduling) so the
dynamic tile-trip bound in eRVS actually bites.  Because random streams
are keyed per query (not per slot), results are bit-identical for any
slot count / epoch length.

Multi-device (docs/scaling.md): ``run(..., devices=N)`` shards the slot
pool over a 1D ``"walkers"`` mesh — each device owns a contiguous block
of slots, the single host-side queue refills them *round-robin across
devices* so no device starves while another queues work, and the jitted
epoch runs as one GSPMD program with the graph replicated.  Telemetry
stays exact: ``StepStats`` counters are integer sums over live lanes, a
cross-device reduction with no ordering freedom, so ``frac_rjs`` /
``frac_precomp`` are identical to the single-device run — as are the
paths, because RNG streams are per query (topology invariance is the
batch-invariance contract, extended).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import deque
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flexi_compiler as fc
from repro.core import precomp as precomp_mod
from repro.core.cost_model import CostModel
from repro.core.ctxutil import degrees_of
from repro.core.samplers import (PRECOMP_EXEC_CHOICES, SamplerContext,
                                 available_samplers, get_sampler,
                                 resolve_precomp_exec)
from repro.core.types import (EdgeCtx, StepStats, WalkerState, WalkProgram,
                              Workload, from_workload)
from repro.distributed import sharding as shd
from repro.graphs.csr import CSRGraph, search_depth
from repro.graphs import node_stats
from repro.graphs.delta import GraphDelta, UpdateReport, host_row_layout
# DMA block size of the mega-step kernel (kernels/ref.py is jnp-only —
# importing the constant never loads the Pallas modules)
from repro.kernels.ref import TILE as KERNEL_TILE

# Snapshot of the built-in registry (kept for CLI choices / legacy imports);
# the registry itself is the source of truth and accepts custom samplers.
METHODS = available_samplers()

DEFAULT_EPOCH_LEN = 16

# Step execution paths (EngineConfig.step_exec): "staged" = the lax.scan
# step loop below; "fused" = the kernels/megastep_kernel.py mega-step (one
# Pallas kernel per epoch, no XLA round-trips between DMA / weight eval /
# regime pick / hooks); "auto" = fused on TPU when the (sampler × program)
# cell is provably fusable, staged everywhere else.  Both paths consume
# the same counter-based Threefry streams and are bit-identical — the
# knob is throughput only, and non-fusable cells silently keep the staged
# scan (WalkEngine.step_exec_resolved reports the decision).
STEP_EXEC_CHOICES = ("auto", "fused", "staged")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    method: str = "adaptive"
    tile: int = 256
    rjs_trials: int = 8
    rjs_max_rounds: int = 16
    cost_model: CostModel = dataclasses.field(default_factory=CostModel)
    seed: int = 0
    # "degree" selection strategy threshold (Fig. 13 baseline)
    degree_threshold: int = 1024
    # degree at which PartitionedSampler's reservoir side switches from
    # plain eRVS to the A-ExpJ jump variant (per-node reservoir choice:
    # the jump bookkeeping only pays for itself on long rows)
    jump_threshold: int = 1024
    # scan steps per scheduler epoch.  None → one full-walk epoch when
    # every query has a slot (nothing to refill, no host syncs mid-walk),
    # else min(walk length, 16).  Slots are refilled from the host queue
    # only at epoch boundaries, so smaller epochs reclaim dead lanes
    # sooner at the cost of more host syncs.
    epoch_len: Optional[int] = None
    # execution path for precomputed-table draws: "pallas" = the
    # kernels/precomp_kernel.py DMA kernels (interpret mode off-TPU),
    # "jnp" = the core/precomp.py selectors, "auto" = pallas on TPU, jnp
    # elsewhere.  Bit-identical either way — this knob is throughput only.
    precomp_exec: str = "auto"
    # stale precomp rows re-baked per scheduler epoch (amortized background
    # rebuild after update_graph invalidations); 0 disables draining, so
    # stale rows keep the dynamic fallback until drain_rebuilds() is
    # called explicitly.
    rebuild_budget: int = 8
    # drain the rebuild queue only every K-th scheduler epoch, with a
    # K×-sized batch (same amortized rate, fewer host round-trips — each
    # drain is one jitted scatter regardless of row count).  1 = drain
    # every epoch (the original cadence).  Like the epoch cadence itself,
    # this only matters while the queue is non-empty (see run()'s batch-
    # invariance note).
    rebuild_interval: int = 1
    # fold the structural delta overlay (apply_updates) back into a
    # contiguous CSR every K-th engine epoch, on the engine-absolute
    # epoch clock (so the cadence is a property of the engine's
    # timeline, not of any one run's loop).  0 = never compact
    # automatically; call WalkEngine.compact() explicitly.
    compact_interval: int = 0
    # step execution path: see STEP_EXEC_CHOICES above.  Bit-identical
    # either way; "fused" on a non-fusable (sampler × program) cell keeps
    # the staged scan rather than erroring.
    step_exec: str = "auto"

    def __post_init__(self):
        if self.method not in available_samplers():
            raise ValueError(
                f"method {self.method!r} does not name a registered "
                f"sampler; known samplers: "
                f"{', '.join(available_samplers())}")
        if self.precomp_exec not in PRECOMP_EXEC_CHOICES:
            raise ValueError(
                f"precomp_exec {self.precomp_exec!r} does not name a "
                f"table-draw execution path; valid choices: "
                f"{', '.join(PRECOMP_EXEC_CHOICES)}")
        if self.rebuild_budget < 0:
            raise ValueError(
                f"rebuild_budget must be >= 0 (stale table rows re-baked "
                f"per scheduler epoch; 0 disables background rebuilds), "
                f"got {self.rebuild_budget}")
        if self.rebuild_interval < 1:
            raise ValueError(
                f"rebuild_interval must be >= 1 (drain the rebuild queue "
                f"every K-th scheduler epoch), got {self.rebuild_interval}")
        if self.compact_interval < 0:
            raise ValueError(
                f"compact_interval must be >= 0 (fold the structural "
                f"overlay into a fresh CSR every K-th engine epoch; 0 "
                f"keeps compaction explicit-only), "
                f"got {self.compact_interval}")
        if self.step_exec not in STEP_EXEC_CHOICES:
            raise ValueError(
                f"step_exec {self.step_exec!r} does not name a step "
                f"execution path; valid choices: "
                f"{', '.join(STEP_EXEC_CHOICES)}")


@dataclasses.dataclass
class WalkResult:
    paths: np.ndarray  # [Q, L+1] int32; -1 marks termination
    frac_rjs: float  # fraction of live steps served by eRJS (Fig. 14)
    rjs_fallbacks: int
    steps: int
    live_steps: int = 0  # total live walker-steps (the frac_rjs denominator)
    # fraction of live steps served from precomputed ITS/alias tables
    # (nonzero only for static-provable workloads in the precomp regime)
    frac_precomp: float = 0.0
    # fraction of live steps that hit a stale (invalidated) table row and
    # fell back to the dynamic path — transient: drops to 0 once the
    # rebuild queue has re-baked every invalidated row
    frac_stale: float = 0.0
    # stale table rows re-baked by this run's per-epoch queue drains
    rebuilt_rows: int = 0
    # per-device work distribution for sharded runs (run(..., devices=N)):
    # one dict per device — {"device", "slots", "queries", "emitted_steps"}.
    # None for single-device runs.  Aggregate telemetry above is already
    # the exact cross-device reduction; this is the balance diagnostic.
    per_device: Optional[list] = None


@dataclasses.dataclass
class EpochReport:
    """What one scheduler epoch did — the epoch-boundary view a driver
    (``WalkEngine.run`` or ``repro.serving.WalkService``) schedules
    against."""

    #: query ids whose walkers finished this epoch (walked ``num_steps``,
    #: dead-ended, or stopped via ``should_stop``) — their slots are free
    completed: np.ndarray
    #: steps each completed query actually walked (aligned with
    #: ``completed``; < num_steps for dead ends / early stops)
    steps_taken: np.ndarray
    #: slots occupied while the epoch ran
    occupied: int
    #: this epoch's integer telemetry sums (``StepStats.host_totals`` keys)
    stats: dict

    @property
    def walker_steps(self) -> int:
        """Live walker-steps this epoch actually served (the ``live``
        telemetry sum) — the work unit the serving loop's deficit-round-
        robin fairness scheduler charges against a tenant's credit.  Pad
        slots, finished walkers and dead lanes never count, so an epoch
        over a mostly-empty pool is cheap in deficit terms exactly like
        it is cheap in arithmetic."""
        return int(self.stats.get("live", 0))


class EpochScheduler:
    """Host-side driver of one engine's jitted epoch — the streaming
    scheduler of §5.3 as a reusable object.

    ``WalkEngine.run`` is a thin loop over this class (admit everything,
    step until drained); ``repro.serving.WalkService`` drives the same
    object as a long-lived serving loop, admitting queries from concurrent
    clients at epoch boundaries.  Because both paths share the slot pool,
    refill scatter, path harvest and telemetry accumulation — and random
    streams are keyed per *query id* (``fold_in(key, qid)``), never per
    slot or epoch — a query's served path is bit-identical no matter which
    driver ran it or when it was admitted (the scheduler contract
    documented on ``run``).

    Epoch-boundary hooks
    --------------------
    * :meth:`free_slots` — slots available for admission (round-robin
      across devices under a mesh).
    * :meth:`admit` — install queries into free slots without retrace:
      a refilled slot gets ``step=0``, ``prev=-1``, ``alive=True``, the
      query's own stream key, and a fresh ``init_walker_state(qid)``.
    * :meth:`run_epoch` — drain the engine's rebuild queue on its
      cadence, execute one jitted epoch, harvest emitted path entries,
      and report which queries completed.
    * :meth:`kill` — clear lanes' alive bits host-side (the serving
      loop's deadline enforcement: the walker emits nothing further and
      stops counting toward telemetry, exactly like a ``should_stop``
      verdict folding into the alive mask).

    Query ids are caller-assigned: they pick the RNG stream
    (``fold_in(key, qid)``) and index into :attr:`paths`, which grows on
    demand (``run`` sizes it exactly; the serving loop admits unbounded
    streams).
    """

    def __init__(self, engine: "WalkEngine", num_steps: int, key,
                 slots: int, epoch_len: int, mesh=None, n_dev: int = 1,
                 capacity: int = 0, track_tables: bool = False):
        self.engine = engine
        self.num_steps = int(num_steps)
        self.key = key
        self.W = int(slots)
        self.T = int(epoch_len)
        self.mesh = mesh
        self.n_dev = int(n_dev)
        #: serve every epoch from this pinned view of the precomp tables,
        #: NOT from engine.precomp — background drains repair the engine's
        #: copy without flipping any row's regime mid-run (the batch-
        #: invariance contract of run(); drains become visible to the
        #: next scheduler, or immediately with track_tables=True, the
        #: serving loop's epoch-granular mode)
        # pins tables + graph/stats/pad views and records the engine
        # mutation epoch; a clock bump (weight or structural mutation)
        # forces a re-pin — the old views index a dead row layout /
        # stale payloads (see run_epoch)
        self.adopt_tables()
        self.track_tables = bool(track_tables)
        # slots per device (device d owns [d·spd, (d+1)·spd))
        self.spd = self.W // self.n_dev
        #: [Q, num_steps+1] harvested paths, -1 past termination; row q
        #: belongs to query id q (grown on demand for streaming drivers)
        self.paths = np.full((int(capacity), self.num_steps + 1), -1,
                             np.int32)
        #: query id each slot serves (-1 = free)
        self.slot_query = np.full(self.W, -1, np.int64)
        #: accumulated StepStats.host_totals over every epoch run so far
        self.totals = dict.fromkeys(
            (f.name for f in dataclasses.fields(StepStats)), 0)
        self.rebuilt_rows = 0
        self.epoch_idx = 0
        self.dev_queries = np.zeros(self.n_dev, np.int64)
        self.dev_steps = np.zeros(self.n_dev, np.int64)
        kd_shape = jax.random.key_data(key).shape
        state = WalkerState(
            cur=jnp.zeros((self.W,), jnp.int32),
            prev=jnp.full((self.W,), -1, jnp.int32),
            step=jnp.full((self.W,), self.num_steps, jnp.int32),
            alive=jnp.zeros((self.W,), bool),
            rng=jnp.zeros((self.W,) + kd_shape, jnp.uint32),
            carry=engine.sampler.init_carry(engine.sampler_ctx, self.W),
            # program-owned per-walker state: placeholder rows until a
            # refill installs the query's own init_walker_state(q)
            wstate=engine.workload.init_wstate_batch(
                jnp.zeros((self.W,), jnp.int32)),
        )
        if mesh is not None:
            state = shd.shard_walker_state(state, self.W, mesh)
        self.state = state

    # ------------------------------------------------------------- queries
    @property
    def busy(self) -> bool:
        """Whether any slot still serves a query."""
        return bool((self.slot_query >= 0).any())

    @property
    def occupancy(self) -> int:
        """Slots currently serving a query (never exceeds ``W``)."""
        return int((self.slot_query >= 0).sum())

    def in_flight(self) -> np.ndarray:
        """Query ids currently occupying slots."""
        return self.slot_query[self.slot_query >= 0].copy()

    def free_slots(self) -> np.ndarray:
        """Admittable slot indices.  Under a mesh they come round-robin
        across devices (every device's first free slot before any
        device's second), so one busy device cannot leave another starved
        while queries queue."""
        free = np.nonzero(self.slot_query < 0)[0]
        if self.mesh is not None and free.size:
            free = free[np.argsort((free % self.spd) * self.n_dev
                                   + free // self.spd, kind="stable")]
        return free

    def _ensure_capacity(self, n: int) -> None:
        if n <= self.paths.shape[0]:
            return
        cap = max(n, 2 * self.paths.shape[0], 64)
        grown = np.full((cap, self.num_steps + 1), -1, np.int32)
        grown[:self.paths.shape[0]] = self.paths
        self.paths = grown

    @functools.partial(jax.profiler.annotate_function, name="sched.admit")
    def admit(self, query_ids, starts) -> int:
        """Install queries into free slots (epoch-boundary refill), inside
        the profiler span ``sched.admit``.

        ``query_ids`` pick the RNG streams and path rows; the caller must
        not exceed ``free_slots()``.  Returns how many were admitted.
        """
        qs = np.asarray(query_ids, np.int64).reshape(-1)
        if qs.size == 0:
            return 0
        starts = np.asarray(starts, np.int32).reshape(-1)
        free = self.free_slots()
        if qs.size > free.size:
            raise ValueError(
                f"admit() got {qs.size} queries but only {free.size} "
                f"slots are free; consult free_slots() first")
        self._ensure_capacity(int(qs.max()) + 1)
        self.paths[qs, 0] = starts
        take = free[:qs.size]
        self.slot_query[take] = qs
        if self.mesh is not None:
            np.add.at(self.dev_queries, take // self.spd, 1)
        idx = jnp.asarray(take, jnp.int32)
        qkeys = WalkerState.stream_key_data(
            self.key, jnp.asarray(qs, jnp.int32))
        state = self.state
        self.state = WalkerState(
            cur=state.cur.at[idx].set(jnp.asarray(starts)),
            prev=state.prev.at[idx].set(-1),
            step=state.step.at[idx].set(0),
            alive=state.alive.at[idx].set(True),
            rng=state.rng.at[idx].set(qkeys),
            # sampler carry survives refills untouched: samplers validate
            # it per lane (a prefetch tile is tagged with its node, so a
            # new occupant simply misses)
            carry=state.carry,
            # program state is reset per QUERY (like the RNG stream), so
            # results stay placement-invariant
            wstate=jax.tree_util.tree_map(
                lambda leaf, new: leaf.at[idx].set(new),
                state.wstate,
                self.engine.workload.init_wstate_batch(
                    jnp.asarray(qs, jnp.int32))),
        )
        if self.mesh is not None:
            # re-assert the walker layout: the scatter above may leave
            # the refilled leaves with a gathered sharding
            self.state = shd.shard_walker_state(self.state, self.W,
                                                self.mesh)
        return int(qs.size)

    def kill(self, query_ids) -> np.ndarray:
        """Retire the lanes serving ``query_ids`` NOW (the serving loop's
        deadline enforcement).  Clears their ``alive`` bits — like a
        ``should_stop`` verdict, the walker emits nothing further and
        stops counting toward telemetry — and frees their slots for the
        next admission.  Harvested path prefixes stay in :attr:`paths`.
        Returns the query ids actually found in flight."""
        qs = np.asarray(query_ids, np.int64).reshape(-1)
        if qs.size == 0:
            return qs
        idx_np = np.nonzero(np.isin(self.slot_query, qs))[0]
        if idx_np.size == 0:
            return self.slot_query[idx_np]  # empty
        killed = self.slot_query[idx_np].copy()
        idx = jnp.asarray(idx_np, jnp.int32)
        self.state = dataclasses.replace(
            self.state, alive=self.state.alive.at[idx].set(False))
        if self.mesh is not None:
            self.state = shd.shard_walker_state(self.state, self.W,
                                                self.mesh)
        self.slot_query[idx_np] = -1
        return killed

    # ------------------------------------------------------- table pinning
    def adopt_tables(self) -> None:
        """Re-pin this scheduler's serving view on the engine's current
        precomp tables — plus the graph/stats/pad views the jitted epoch
        now takes as arguments — and record the engine mutation epoch the
        view reflects.  Called automatically when a graph mutation bumps
        the engine's mutation clock, and every epoch under
        ``track_tables=True``; call it directly to make a just-drained
        repair visible mid-run."""
        eng = self.engine
        if self.mesh is None:
            self.tables, self.graph_view, self.stats_view = (
                eng.precomp, eng.graph, eng.stats)
        else:
            self.tables, self.graph_view, self.stats_view = (
                eng.replicated_views(self.mesh))
        self.pad_view = eng.pad
        self.max_tiles_view = eng.max_tiles
        self._mutation_seen = eng.mutation_clock

    def reset_sampler_carry(self) -> None:
        """Re-initialise the sampler-owned cross-step carry (e.g. the
        interleaved sampler's prefetch tile, which caches edge payloads
        gathered from the pre-mutation graph).  Bit-neutral while the
        graph is unchanged — a cold tile re-gathers the same values — and
        required after a weight or structural mutation so in-flight
        walkers read post-mutation payloads, exactly like a fresh
        engine's walkers would."""
        eng = self.engine
        self.state = dataclasses.replace(
            self.state,
            carry=eng.sampler.init_carry(eng.sampler_ctx, self.W))
        if self.mesh is not None:
            self.state = shd.shard_walker_state(self.state, self.W,
                                                self.mesh)

    # -------------------------------------------------------------- epochs
    @functools.partial(jax.profiler.annotate_function,
                       name="sched.run_epoch")
    def run_epoch(self) -> EpochReport:
        """Compact / drain on the engine-absolute cadences, execute one
        jitted epoch (``T`` scan steps) against the pinned table view,
        harvest emitted path entries, and report completions.

        Runs inside the profiler span ``sched.run_epoch``, whose children
        split its host time in order: ``sched.maintain`` (compaction,
        re-pin, rebuild drain), ``sched.dispatch`` (the step pull and the
        epoch program's enqueue), ``sched.wait`` (blocked on the device
        for the emitted nodes) and ``sched.harvest`` (path scatter,
        telemetry, completions)."""
        eng = self.engine
        with jax.profiler.TraceAnnotation("sched.maintain"):
            self._maintain()
        # Serve against the PINNED graph/stats/table views (re-pinned
        # above on any mutation-clock bump) — run_epoch_fn resolves
        # fused-vs-staged per epoch, so a mutation mid-serve flips the
        # path the moment the engine's streams change.  Sharded runs keep
        # the staged scan: the mega-step kernel is one Pallas program
        # over the whole lane pool, and mixing it with a GSPMD-
        # partitioned epoch would change nothing but plumbing — both
        # paths are bit-identical, so this is purely an exec choice.
        with jax.profiler.TraceAnnotation("sched.dispatch"):
            step0 = np.asarray(self.state.step)
            self.state, emitted, stats = eng.run_epoch_fn(
                self.state, self.tables, self.graph_view, self.stats_view,
                epoch_len=self.T, num_steps=self.num_steps,
                pad=self.pad_view, max_tiles=self.max_tiles_view,
                fused=(self.mesh is None), shards=self.n_dev)
        with jax.profiler.TraceAnnotation("sched.wait"):
            emitted = np.asarray(emitted)  # [T, W]
        with jax.profiler.TraceAnnotation("sched.harvest"):
            return self._harvest(step0, emitted, stats)

    def _maintain(self) -> None:
        """The epoch boundary's upkeep before dispatch: scheduled
        compaction, re-pin after a mutation, the rebuild drain, and the
        epoch clocks."""
        eng = self.engine
        cfg = eng.config
        # scheduled overlay compaction (config.compact_interval), keyed —
        # like the drain cadence below — to the ENGINE-absolute epoch
        # clock, so when the overlay folds back into a contiguous CSR is
        # a property of the engine's timeline, not of which run happens
        # to be looping.  compact() bumps the mutation clock, so the
        # re-pin below picks up the re-laid tables in the same epoch.
        if (eng.overlay_active and cfg.compact_interval
                and eng.epoch_clock % cfg.compact_interval == 0):
            eng.compact()
        # Pinned-table contract: a graph mutation (apply_updates /
        # update_graph / compact) bumped the engine's mutation clock —
        # the pinned view indexes a dead row layout (structural) or
        # pre-mutation payloads cached in the sampler carry (weights),
        # so re-pin and reset the carry.  Absent mutations the view
        # stays fixed for the scheduler's whole life: background drains
        # repair engine-side only, which is what makes paths invariant
        # to the epoch cadence even while a rebuild is in flight.
        if eng.mutation_clock != self._mutation_seen:
            self.adopt_tables()
            self.reset_sampler_carry()
        # amortized background rebuild: re-bake a budgeted few stale
        # table rows while the walkers run (host work between jitted
        # epochs; the tables are an epoch *argument*, so no retrace).
        # cfg.rebuild_interval batches the drains: every K-th engine
        # epoch re-bakes a K×budget batch — same amortized rate, one
        # jitted scatter per drain instead of K.  scatter="copy": the
        # pinned view may alias the drained buffers, and donating them
        # would invalidate the view mid-run (explicit drain_rebuilds()
        # calls keep the donating fast path).
        if (eng.precomp is not None and cfg.rebuild_budget
                and len(eng.rebuild_queue)
                and eng.epoch_clock % cfg.rebuild_interval == 0):
            self.rebuilt_rows += eng.drain_rebuilds(
                cfg.rebuild_budget * cfg.rebuild_interval, scatter="copy")
        # serving-loop mode: adopt the engine's tables every epoch, AFTER
        # the drain, so repairs become visible at epoch granularity (the
        # piecewise-deterministic serving contract — see WalkService)
        if self.track_tables:
            self.adopt_tables()
        self.epoch_idx += 1
        eng.epoch_clock += 1

    def _harvest(self, step0: np.ndarray, emitted: np.ndarray,
                 stats: StepStats) -> EpochReport:
        """Scatter the epoch's emitted nodes into :attr:`paths`, add its
        telemetry to :attr:`totals`, and free the finished walkers' slots."""
        step1 = np.asarray(self.state.step)
        alive1 = np.asarray(self.state.alive)
        occupied = np.nonzero(self.slot_query >= 0)[0]
        taken = step1[occupied] - step0[occupied]
        s0 = step0[occupied]
        if s0.size and (s0 == s0[0]).all():
            # homogeneous epoch (incl. the full-batch single-epoch
            # case): one vectorized write; the -1s emitted after a
            # lane stops are exactly the termination padding.
            base = int(s0[0])
            width = min(self.T, self.num_steps - base)
            self.paths[self.slot_query[occupied],
                       base + 1:base + 1 + width] = \
                emitted[:width, occupied].T
        else:
            for t in range(int(taken.max(initial=0))):
                sel = occupied[taken > t]
                self.paths[self.slot_query[sel],
                           step0[sel] + 1 + t] = emitted[t, sel]
        ep = stats.host_totals()
        for k in self.totals:
            self.totals[k] += ep[k]
        if self.mesh is not None:
            self.dev_steps += (emitted >= 0).sum(axis=0) \
                .reshape(self.n_dev, self.spd).sum(axis=1)
        done = occupied[(~alive1[occupied])
                        | (step1[occupied] >= self.num_steps)]
        completed = self.slot_query[done].copy()
        steps_taken = step1[done].copy()
        self.slot_query[done] = -1
        return EpochReport(completed=completed, steps_taken=steps_taken,
                           occupied=int(occupied.size), stats=ep)


class WalkEngine:
    """End-to-end dynamic walk executor for one (graph, walk program).

    ``workload`` is a :class:`~repro.core.types.WalkProgram` — or the
    deprecated :class:`~repro.core.types.Workload` / any duck-typed legacy
    object, which is adapted via :func:`~repro.core.types.from_workload`
    with bit-identical results.
    """

    def __init__(self, graph: CSRGraph, workload: WalkProgram,
                 config: Optional[EngineConfig] = None):
        self.graph = graph
        if not isinstance(workload, WalkProgram):
            workload = from_workload(workload)  # duck-typed legacy object
        self.workload = workload
        self.config = config or EngineConfig()
        try:
            self.sampler = get_sampler(self.config.method)
        except KeyError:
            raise ValueError(
                f"method must name a registered sampler; "
                f"have {available_samplers()}") from None
        self.stats = node_stats(graph, num_labels=max(workload.num_labels, 1))
        self.compiled = fc.analyze(workload)
        self.max_degree = int(graph.max_degree())
        self.pad = max(1 << (self.max_degree - 1).bit_length(), self.config.tile)
        self.max_tiles = math.ceil(self.pad / self.config.tile)
        # Mega-step plan: can (sampler × program) run as ONE fused Pallas
        # kernel per epoch?  Needs the Flexi-Compiler's fusability proof
        # (fuse_report), a sampler-declared fused regime, and kernel tile
        # geometry; "rejection" additionally needs the compiled bound to
        # be node-local so it can be baked into a per-node table.
        self.fuse = fc.fuse_report(workload)
        will_precomp = (self.sampler.caps.needs_precomp
                        and fc.is_static(workload))
        self._fused_kind = self._plan_fused_kind(will_precomp)
        # Precomputed-regime tables (C-SAW-style): built once iff the
        # sampler asked for them (caps.needs_precomp) AND the Flexi-
        # Compiler proves get_weight state-independent.  Dynamic workloads
        # leave this None and precomp-capable samplers degrade to eRVS.
        self.precomp = None
        if will_precomp:
            # the tile-aligned kernel streams are only materialised when
            # a resolved execution path will actually DMA them — the
            # per-draw Pallas kernels or the fused mega-step table regime
            aligned = (resolve_precomp_exec(
                self.config.precomp_exec) == "pallas"
                or (self._fused_kind or "").startswith("precomp"))
            with jax.profiler.TraceAnnotation("engine.build_tables"):
                self.precomp = precomp_mod.build_tables(
                    graph, workload, compiled_params(workload),
                    aligned=aligned)
        # stale rows queued by update_graph, drained a budgeted few per
        # scheduler epoch (config.rebuild_budget) / via drain_rebuilds()
        self.rebuild_queue = precomp_mod.RebuildQueue()
        # structural delta overlay (apply_updates): None while the graph
        # is a contiguous CSR; a GraphDelta while edits are pending, with
        # self.graph the matching OverlayGraph until compact() folds it
        self.delta: Optional[GraphDelta] = None
        # engine-absolute epoch counter: every scheduler epoch ever run
        # against this engine advances it, so rebuild/compaction cadences
        # are properties of the engine's timeline, not of any one run's
        # loop-local index
        self.epoch_clock = 0
        # bumped by every graph mutation (update_graph / apply_updates /
        # compact); schedulers compare it against the value their pinned
        # table view was taken at and re-pin on mismatch
        self.mutation_clock = 0
        self.sampler_ctx = SamplerContext(
            graph=graph, workload=workload, params=compiled_params(workload),
            compiled=self.compiled, stats=self.stats, config=self.config,
            pad=self.pad, max_tiles=self.max_tiles, precomp=self.precomp)
        # trace-time side-effect counters: incremented by a Python
        # statement inside the traced epoch bodies, so they count actual
        # XLA compilations, not calls — the retrace-bound regression
        # (tests/test_structural.py) pins mutation bursts to O(log K)
        self.staged_traces = 0
        self.fused_traces = 0
        # walker mesh -> {view name: (source, replicated copy)}, see
        # replicated_views
        self._replicas: dict = {}
        # Both epochs are jitted ONCE per engine: everything a mutation
        # changes (graph, stats, tables, edge streams) enters as a
        # runtime argument, so a mutation retraces only when an argument
        # SHAPE (or the graph's pytree type) changes — and the overlay's
        # pow2 patch capacity + the sticky pow2 pad bucket those shapes.
        self._epoch_fn = jax.jit(
            self._make_epoch(),
            static_argnames=("epoch_len", "num_steps", "pad", "max_tiles",
                             "shards"))
        self._fused_epoch_fn = (self._build_fused_epoch()
                                if self._fused_kind else None)
        self._fused_streams = None
        self._refresh_fused_streams()

    @property
    def dist_depth(self) -> int:
        """Rounds of the staged path's ``dist`` search: ``search_depth``
        of the padded row length ``pad``, so it moves only with ``pad``
        (``StepStats.dist_searches`` times this is the search's gather
        rounds)."""
        return search_depth(self.pad)

    # ------------------------------------------------------ fused planning
    @property
    def step_exec_resolved(self) -> str:
        """The step execution path this engine actually runs for
        single-device epochs: "fused" or "staged" (sharded epochs always
        run staged — see run()).  Reservoir/rejection regimes keep the
        fused kernel while a structural overlay is active; precomp
        regimes stand down to the staged scan until compact() re-attaches
        the aligned table streams."""
        return ("fused" if self._fused_epoch_fn is not None
                and self._fused_streams is not None else "staged")

    def _plan_fused_kind(self, will_precomp: bool):
        """Resolve ``config.step_exec`` against the fusability analysis:
        the mega-step regime to run, or None → staged scan."""
        cfg = self.config
        if cfg.step_exec == "staged":
            return None
        if cfg.step_exec == "auto" and jax.default_backend() != "tpu":
            # interpret-mode fused epochs are a test vehicle, not a win;
            # opt in explicitly with step_exec="fused"
            return None
        if not self.fuse.fusable:
            return None
        kind = self.sampler.fused_kind(usable=self.compiled.usable,
                                       has_precomp=will_precomp)
        if kind is None:
            return None
        if kind == "rejection" and not self.fuse.bound_node_local:
            # the kernel reads a per-NODE bound table; a bound that also
            # depends on prev/step/wstate cannot be baked.  Never downgrade
            # to the reservoir regime (different telemetry) — stay staged.
            return None
        tile = cfg.tile
        if tile < 2 or tile % 2 or KERNEL_TILE % tile:
            return None  # kernel DMA geometry (see megastep_kernel)
        return kind

    def _bake_bmax(self) -> jnp.ndarray:
        """Per-node rejection bound table for the fused kernel.  Sound
        because the plan requires ``fuse.bound_node_local``: the compiled
        bound provably ignores prev/step/wstate, so evaluating it at a
        placeholder walker context gives every walker's bound at v."""
        V = int(self.graph.num_nodes)
        nodes = jnp.arange(V, dtype=jnp.int32)
        ws = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (V,) + l.shape),
            self.workload.wstate_template())
        bi = fc.BoundInputs(
            h_min=self.stats.h_min, h_max=self.stats.h_max,
            h_mean=self.stats.h_mean,
            deg_cur=jnp.asarray(self.graph.degrees(), jnp.int32),
            deg_prev=jnp.zeros((V,), jnp.int32),
            cur=nodes, prev=jnp.full((V,), -1, jnp.int32),
            step=jnp.zeros((V,), jnp.int32), wstate=ws)
        _, bmax = jax.vmap(self.compiled.bound_fn)(bi)
        return bmax

    def _build_fused_epoch(self):
        # deferred so staged-only engines never load the Pallas modules
        from repro.kernels import megastep_kernel
        cfg = self.config
        inner = megastep_kernel.make_streamed_epoch(
            self.workload, compiled_params(self.workload),
            kind=self._fused_kind, tile=cfg.tile,
            rjs_trials=cfg.rjs_trials, rjs_max_rounds=cfg.rjs_max_rounds)
        engine = self

        # XLA module jit_epoch_fused, distinct from jit_epoch_staged
        def epoch_fused(state, precomp, streams, epoch_len: int,
                        num_steps: int, max_tiles: int):
            engine.fused_traces += 1  # trace-time only (see __init__)
            return inner(state, precomp, streams, epoch_len, num_steps,
                         max_tiles)

        return jax.jit(
            epoch_fused,
            static_argnames=("epoch_len", "num_steps", "max_tiles"))

    def _refresh_fused_streams(self) -> None:
        """(Re)build the host-side aligned edge streams the fused
        mega-step consumes, or set them to None when the fused path must
        stand down for the current graph.

        The streams are jit *arguments* (make_streamed_epoch), so a
        mutation re-aligns the touched layout host-side and the kernel
        retraces only when the pow2-bucketed stream shapes change.
        Reservoir/rejection regimes rebuild them for overlay graphs too
        (the kernel body reads per-node deg/row0 streams and never
        assumes contiguity); precomp regimes need the aligned *table*
        streams, which exist only in the compacted layout (grow_tables
        drops them), so they wait for compact()."""
        if self._fused_kind is None:
            self._fused_streams = None
            return
        if self.overlay_active and self._fused_kind.startswith("precomp"):
            self._fused_streams = None
            return
        from repro.kernels import megastep_kernel
        bmax = self._bake_bmax() if self._fused_kind == "rejection" else None
        with jax.profiler.TraceAnnotation("engine.fused_streams"):
            self._fused_streams = megastep_kernel.fused_streams(
                self.graph, self.workload, bmax=bmax,
                bucket_rows=self.overlay_active)

    # ------------------------------------------------------------ epoch fn
    def _make_epoch(self):
        """Build the jitted epoch: ``epoch_len`` scan steps over WalkerState.

        ``epoch(state, precomp, graph, stats, ...)`` — everything a graph
        mutation changes enters as a runtime *argument* (PrecompTables,
        CSRGraph/OverlayGraph and NodeStats are registered pytrees), not
        a closed-over constant: between-epoch rebuild drains swap in
        re-baked rows with no retrace, and a structural/weight mutation
        swaps in the new graph view the same way.  ``pad``/``max_tiles``
        ride along as *static* args.  The epoch is jitted once per
        engine, so a K-burst mutation storm retraces only once per
        distinct (graph pytree type, array-shape bucket, pad) combination
        — O(log K) with the overlay's pow2 patch capacity and the sticky
        pow2 pad.  Returns ``(state', emitted [T, W], StepStats of
        [T]-arrays)`` where ``emitted[t, s]`` is the node slot ``s`` moved
        to at scan step t (-1 when it did not step).  Lanes past
        ``num_steps`` are masked, so an epoch may safely overshoot a
        walker's remaining budget.
        """
        sampler = self.sampler
        base_ctx = self.sampler_ctx
        program = self.workload
        params = self.sampler_ctx.params
        engine = self

        def transition_ctx(graph, state: WalkerState, nxt, deg_cur
                           ) -> EdgeCtx:
            """Per-walker EdgeCtx of the transition just taken (the
            WalkProgram hook contract documented on WalkProgram): nbr =
            node moved to, cur/prev/step = pre-move view; per-edge payload
            fields are placeholders (h=1, label=-1, dist=-1)."""
            W = state.cur.shape[0]
            return EdgeCtx(
                h=jnp.ones((W,), jnp.float32),
                label=jnp.full((W,), -1, jnp.int32),
                dist=jnp.full((W,), -1, jnp.int32),
                nbr=nxt,
                deg_cur=deg_cur,
                deg_prev=degrees_of(graph, state.prev),
                cur=state.cur, prev=state.prev, step=state.step,
            )

        def step(state: WalkerState, ctx, num_steps: int
                 ) -> Tuple[WalkerState, jax.Array, StepStats]:
            deg = degrees_of(ctx.graph, state.cur)
            wants = state.alive & (state.step < num_steps)
            live = wants & (deg > 0)
            rng = state.stream_keys()
            sel = sampler.select(ctx, state, rng, active=live)
            nxt = jnp.where(live, sel.next_nodes, -1)
            stepped = live & (nxt >= 0)
            # ---- WalkProgram hooks: state transition + early termination.
            # Both see the transition ctx; on_step only commits on lanes
            # that moved, and a True should_stop folds into the alive mask
            # so the walker emits nothing further, stops counting toward
            # telemetry, and frees its slot at the next epoch boundary.
            new_wstate = state.wstate
            stop = jnp.zeros_like(stepped)
            if program.has_hooks:
                tctx = transition_ctx(ctx.graph, state, nxt, deg)
                if program.on_step is not None:
                    cand = jax.vmap(program.on_step, in_axes=(0, None, 0))(
                        tctx, params, state.wstate)
                    new_wstate = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(
                            stepped.reshape((-1,) + (1,) * (n.ndim - 1)),
                            n, o),
                        cand, state.wstate)
                if program.should_stop is not None:
                    verdict = jax.vmap(program.should_stop,
                                       in_axes=(0, None, 0))(
                        tctx, params, new_wstate)
                    stop = stepped & verdict
            new_state = WalkerState(
                cur=jnp.where(stepped, nxt, state.cur),
                prev=jnp.where(stepped, state.cur, state.prev),
                step=state.step + stepped.astype(jnp.int32),
                # a lane that wanted to step but could not has dead-ended;
                # a lane whose program said stop is equally finished
                alive=state.alive & ~(wants & ~stepped) & ~stop,
                rng=state.rng,
                # sampler-owned cross-step state (e.g. interleaved's
                # prefetch tile) threads through the scan untouched
                carry=sel.carry if sel.carry is not None else state.carry,
                wstate=new_wstate,
            )
            stats = StepStats(live=jnp.sum(live.astype(jnp.int32)),
                              rjs_served=sel.rjs_served,
                              fallbacks=sel.fallbacks,
                              precomp_served=sel.precomp_served,
                              stale_served=sel.stale_served,
                              ervs_trips=sel.ervs_trips,
                              ervs_edges=sel.ervs_edges,
                              ervs_lane_trips=sel.ervs_lane_trips,
                              dist_searches=sel.dist_searches)
            return new_state, jnp.where(stepped, nxt, -1), stats

        # the function's name is the XLA module's (jit_epoch_staged): the
        # name a profiler trace finds the staged epoch program by
        def epoch_staged(state: WalkerState, precomp, graph, stats,
                         epoch_len: int, num_steps: int, pad: int,
                         max_tiles: int, shards: int):
            engine.staged_traces += 1  # trace-time only (see __init__)
            ctx = dataclasses.replace(base_ctx, precomp=precomp,
                                      graph=graph, stats=stats, pad=pad,
                                      max_tiles=max_tiles, shards=shards)

            def body(carry, _):
                new_state, emitted, stats_t = step(carry, ctx, num_steps)
                return new_state, (emitted, stats_t)

            state, (emitted, step_stats) = jax.lax.scan(
                body, state, None, length=epoch_len)
            return state, emitted, step_stats

        return epoch_staged

    def run_epoch_fn(self, state, tables, graph, stats, *, epoch_len: int,
                     num_steps: int, pad: int, max_tiles: int,
                     fused: bool = True, shards: int = 1):
        """Execute one jitted epoch against explicit graph/stats/table
        views — the single entry point both drivers (EpochScheduler and
        walk_batch) call, so the fused-vs-staged pick lives in one place.
        Runs the fused mega-step when the engine has one AND its edge
        streams exist for the current graph (see _refresh_fused_streams);
        ``fused=False`` forces the staged scan (sharded epochs), and
        ``shards`` is the number of devices its slot axis is block-sharded
        over.  Both paths are bit-identical."""
        if (fused and self._fused_epoch_fn is not None
                and self._fused_streams is not None):
            return self._fused_epoch_fn(
                state, tables, self._fused_streams, epoch_len=epoch_len,
                num_steps=num_steps, max_tiles=max_tiles)
        return self._epoch_fn(state, tables, graph, stats,
                              epoch_len=epoch_len, num_steps=num_steps,
                              pad=pad, max_tiles=max_tiles, shards=shards)

    def replicated_views(self, mesh):
        """(tables, graph, stats) with one copy on every device of
        ``mesh``, so sharded epochs read a local copy instead of pulling
        operands from device 0.  Each view is broadcast once per mesh and
        again only after a mutation or compaction swaps it — not per
        walk_batch call or per scheduler epoch."""
        old = self._replicas.get(mesh, {})
        new = {}
        for name, src in (("tables", self.precomp), ("graph", self.graph),
                          ("stats", self.stats)):
            hit = old.get(name)
            new[name] = (src, hit[1] if hit is not None and hit[0] is src
                         else shd.replicate(src, mesh))
        self._replicas[mesh] = new
        return tuple(view for _, view in new.values())

    # ------------------------------------------------------------ frontend
    def run(self, starts, num_steps: Optional[int] = None,
            key: Optional[jax.Array] = None, batch: Optional[int] = None,
            epoch_len: Optional[int] = None,
            devices: Optional[int] = None) -> WalkResult:
        """Run all queries through the streaming epoch scheduler (§5.3).

        ``batch`` fixes the walker-slot count (default: all queries at
        once); pending queries stream into slots as walkers finish.
        ``devices`` shards the slot pool over a 1D walker mesh of that
        many local devices (default 1; see docs/scaling.md).

        Scheduler contract (established in PR 1, relied on by tests)
        ------------------------------------------------------------
        * **Refill**: slots are refilled from the host-side queue only at
          epoch boundaries.  A refilled slot gets ``step=0``, ``prev=-1``,
          ``alive=True`` and the *query's own* stream key; whatever the
          previous occupant left in the slot is dead residue that the live
          mask hides (see ``WalkerState`` invariants).
        * **Batch invariance**: random streams are keyed per *query*
          (``fold_in(run_key, query_id)``), never per slot, epoch or
          device, so paths and telemetry are bit-identical for ANY
          ``batch`` / ``epoch_len`` / ``devices`` choice — including query
          counts that do not divide the slot count.  This holds even
          while a rebuild is in flight: every epoch serves from the
          table view pinned when the run's scheduler was created, and
          background drains repair the *engine's* tables — on the
          engine-absolute epoch clock — without touching the pinned
          view.  Which steps see a stale row therefore depends only on
          the queue state when the run started, never on the epoch
          cadence.  Repairs become visible to the next run (or
          immediately via an explicit ``drain_rebuilds()`` between
          runs); the serving loop opts into epoch-granular visibility
          instead with ``scheduler(track_tables=True)``.
        * **Telemetry**: ``frac_rjs`` / ``frac_precomp`` are weighted by
          *live* walker-steps only; empty slots, finished walkers and tail
          epochs can never dilute them.  Under sharding the counters are
          integer sums over the (sharded) live lanes — exact regardless of
          device count.
        * Queries are served in start-degree order (degree-similar
          co-scheduling) — per-query results are placement-independent, so
          this only affects which queries share an epoch, not any output.
        * **Sharded refill**: each device owns ``W // devices`` contiguous
          slots; free slots are handed to the queue round-robin *across
          devices* (all devices' slot 0 before anyone's slot 1), so a
          device never idles while the queue is non-empty and another
          device hoards free slots.  The pool is padded up to a multiple
          of ``devices``; pad slots are ordinary empty slots
          (``alive=False``) that refills may later occupy.
        """
        num_steps = self.workload.walk_len if num_steps is None else num_steps
        if num_steps <= 0:
            raise ValueError(f"num_steps must be positive, got {num_steps}")
        if batch is not None and batch <= 0:
            raise ValueError(f"batch must be positive, got {batch}")
        if epoch_len is not None and epoch_len <= 0:
            raise ValueError(f"epoch_len must be positive, got {epoch_len}")
        if devices is not None and devices <= 0:
            raise ValueError(f"devices must be positive, got {devices}")
        n_dev = int(devices or 1)
        key = key if key is not None else jax.random.key(self.config.seed)
        starts = np.asarray(starts, np.int32)
        Q = starts.shape[0]
        if Q == 0:
            return WalkResult(paths=np.full((0, num_steps + 1), -1,
                                            np.int32),
                              frac_rjs=0.0, rjs_fallbacks=0,
                              steps=num_steps)
        W = int(min(batch or Q, Q))
        mesh = None
        if n_dev > 1:
            mesh = shd.walker_mesh(n_dev)
            local = {d.id for d in jax.local_devices()}
            if not all(d.id in local for d in mesh.devices.flat):
                # Host-side refills write directly into the sharded state;
                # multi-host meshes need the pre-staged refill buffers
                # described in docs/scaling.md instead.
                raise NotImplementedError(
                    "run(devices=N) requires a fully-addressable "
                    "(single-process) mesh; see docs/scaling.md")
            W = -(-W // n_dev) * n_dev  # pad: every device owns W/n slots
        # With a slot per query there is nothing to refill: run one full
        # epoch (no host syncs inside the walk, like the pre-streaming
        # engine).  Otherwise default to short epochs so dead/finished
        # slots are reclaimed promptly.
        T = int(epoch_len or self.config.epoch_len
                or (num_steps if W >= Q
                    else min(num_steps, DEFAULT_EPOCH_LEN)))
        T = max(1, min(T, num_steps))

        sched = EpochScheduler(self, num_steps=num_steps, key=key,
                               slots=W, epoch_len=T, mesh=mesh,
                               n_dev=n_dev, capacity=Q)
        # degree-similar co-scheduling: serve queries in start-degree order
        # so co-resident slots share a tight eRVS tile-trip bound.
        deg_np = np.asarray(self.graph.degrees())
        queue = deque(np.argsort(deg_np[starts], kind="stable").tolist())

        while queue or sched.busy:
            free = sched.free_slots()
            if queue and free.size:
                take = min(free.size, len(queue))
                qs = np.asarray([queue.popleft() for _ in range(take)])
                sched.admit(qs, starts[qs])
            sched.run_epoch()

        per_device = None
        if mesh is not None:
            per_device = [
                {"device": d, "slots": sched.spd,
                 "queries": int(sched.dev_queries[d]),
                 "emitted_steps": int(sched.dev_steps[d])}
                for d in range(n_dev)]
        live_total = sched.totals["live"]
        return WalkResult(paths=sched.paths,
                          frac_rjs=sched.totals["rjs_served"]
                          / max(live_total, 1),
                          rjs_fallbacks=sched.totals["fallbacks"],
                          steps=num_steps,
                          live_steps=live_total,
                          frac_precomp=sched.totals["precomp_served"]
                          / max(live_total, 1),
                          frac_stale=sched.totals["stale_served"]
                          / max(live_total, 1),
                          rebuilt_rows=sched.rebuilt_rows,
                          per_device=per_device)

    def scheduler(self, num_steps: Optional[int] = None,
                  key: Optional[jax.Array] = None, slots: int = 64,
                  epoch_len: Optional[int] = None,
                  capacity: int = 0,
                  track_tables: bool = False,
                  devices: Optional[int] = None) -> EpochScheduler:
        """Epoch-boundary admission hook: a long-lived
        :class:`EpochScheduler` over this engine's jitted epoch.

        This is what ``run`` itself drives to completion, exposed so a
        serving loop (``repro.serving.WalkService``) can admit queries
        from concurrent clients at epoch boundaries, stream completions
        back per epoch, and kill lanes past their deadline — all without
        retrace, and with the same per-query-stream bit-identity
        guarantee as a batch ``run``.

        ``track_tables=True`` re-adopts the engine's precomp tables every
        epoch (after the background drain) instead of serving the whole
        scheduler life from the view pinned at construction — the serving
        loop's mode: repairs become visible at epoch granularity, at the
        cost of the cross-run drain-schedule invariance a pinned view
        gives a batch ``run``.

        ``devices`` shards the scheduler's slot pool over a 1D walker
        mesh exactly like ``run(devices=N)``: the pool is padded up to a
        multiple of the device count, free slots are handed out round-
        robin across devices, and — because streams are keyed per query,
        never per slot or device — admitted queries produce bit-identical
        paths and telemetry for any device count.
        """
        num_steps = self.workload.walk_len if num_steps is None else num_steps
        if num_steps <= 0:
            raise ValueError(f"num_steps must be positive, got {num_steps}")
        if slots <= 0:
            raise ValueError(f"slots must be positive, got {slots}")
        if devices is not None and devices <= 0:
            raise ValueError(f"devices must be positive, got {devices}")
        n_dev = int(devices or 1)
        key = key if key is not None else jax.random.key(self.config.seed)
        T = int(epoch_len or self.config.epoch_len
                or min(num_steps, DEFAULT_EPOCH_LEN))
        T = max(1, min(T, num_steps))
        slots = int(slots)
        mesh = None
        if n_dev > 1:
            mesh = shd.walker_mesh(n_dev)
            local = {d.id for d in jax.local_devices()}
            if not all(d.id in local for d in mesh.devices.flat):
                # Same constraint as run(devices=N): host-side refills
                # write directly into the sharded state.
                raise NotImplementedError(
                    "scheduler(devices=N) requires a fully-addressable "
                    "(single-process) mesh; see docs/scaling.md")
            slots = -(-slots // n_dev) * n_dev
        return EpochScheduler(self, num_steps=num_steps, key=key,
                              slots=slots, epoch_len=T, mesh=mesh,
                              n_dev=n_dev, capacity=capacity,
                              track_tables=track_tables)

    def walk_batch(self, starts, key: jax.Array, num_steps: int,
                   devices: Optional[int] = None
                   ) -> Tuple[jax.Array, StepStats]:
        """One fully-occupied jitted batch, no host scheduling: returns
        (paths [W, num_steps] on device, per-step StepStats).  This is the
        entry point for sharded/multi-device runs (walker i's stream is
        fold_in(key, i), so lanes are independent of device placement).

        Pass ``devices=N`` to place the batch on a 1D walker mesh here
        (``N`` must divide the batch; walker i keeps stream
        ``fold_in(key, i)``, so outputs are bit-identical to ``devices=1``)
        — or pre-shard ``starts`` yourself with an arbitrary
        ``NamedSharding`` and leave ``devices`` unset."""
        if devices is not None and devices <= 0:
            raise ValueError(f"devices must be positive, got {devices}")
        starts = jnp.asarray(starts, jnp.int32)
        state = WalkerState.create(
            starts, key,
            # walker i serves query i here, so its program state — like
            # its RNG stream — is keyed by i (run()/walk_batch parity)
            wstate=self.workload.init_wstate_batch(
                jnp.arange(starts.shape[0], dtype=jnp.int32)))
        state = dataclasses.replace(
            state, carry=self.sampler.init_carry(self.sampler_ctx,
                                                 starts.shape[0]))
        if devices is not None and devices > 1:
            W = int(starts.shape[0])
            if W % devices:
                raise ValueError(
                    f"devices={devices} must divide the batch ({W}); pad "
                    f"the batch or use run(), which pads its slot pool")
            mesh = shd.walker_mesh(devices)
            state = shd.shard_walker_state(state, W, mesh)
            tables, graph, stats = self.replicated_views(mesh)
        else:
            tables, graph, stats = self.precomp, self.graph, self.stats
        _, emitted, stats = self.run_epoch_fn(
            state, tables, graph, stats,
            epoch_len=num_steps, num_steps=num_steps, pad=self.pad,
            max_tiles=self.max_tiles,
            fused=(devices is None or devices <= 1), shards=devices or 1)
        return emitted.T, stats

    # -------------------------------------------------------- graph updates
    @property
    def overlay_active(self) -> bool:
        """Whether structural edits are pending in the delta overlay (the
        engine is serving an :class:`~repro.graphs.delta.OverlayGraph`;
        :meth:`compact` folds it back into a contiguous CSR)."""
        return self.delta is not None

    def _refresh_epoch_fns(self) -> None:
        """Refresh the sampler context around the current
        graph/stats/tables/pad and bump the mutation clock so live
        schedulers re-pin their serving views (EpochScheduler.run_epoch).

        The jitted epochs themselves are NOT rebuilt: they were jitted
        once in ``__init__`` with graph/stats/tables/streams as runtime
        arguments, so a mutation costs a retrace only when an argument
        shape changes — and the overlay's pow2 patch capacity plus the
        sticky pow2 pad (``_set_pad(floor=...)``) bucket those shapes to
        O(log K) variants across a K-burst mutation storm."""
        self.sampler_ctx = dataclasses.replace(
            self.sampler_ctx, graph=self.graph, stats=self.stats,
            precomp=self.precomp, pad=self.pad, max_tiles=self.max_tiles)
        self.mutation_clock += 1

    def _set_pad(self, max_degree: int, *, floor: int = 0) -> None:
        # identical to the __init__ formula — the fuzzer's fresh-build
        # oracle relies on pad/max_tiles (and hence the eRVS tile-trip
        # bound and the ITS and dist search depths) matching a
        # from-scratch engine.  ``floor`` keeps the pad monotone across
        # overlay applies (sticky pow2 bucketing, so a mutation burst
        # reuses the jitted epoch instead of flapping between pad
        # shapes); oversizing is bit-neutral — ITS and dist search
        # iterations past convergence are no-ops, eRVS tile trips are
        # clamped by live degrees, and padded-row weight baselines mask
        # the extra lanes.  compact() calls with the default floor,
        # restoring the exact fresh-build formula.
        self.max_degree = int(max_degree)
        self.pad = max(1 << (self.max_degree - 1).bit_length(),
                       self.config.tile, int(floor))
        self.max_tiles = math.ceil(self.pad / self.config.tile)

    def update_graph(self, graph: CSRGraph, invalidated=()) -> None:
        """Swap in a graph whose *edge weights* (``h``) were mutated.

        The topology (indptr/indices) must be unchanged — this is the
        weight-only fast path the precomp regime's invalidation bitmap
        exists for; it never creates a delta overlay.  For structural
        changes (edge inserts/deletes) use :meth:`apply_updates`.
        ``invalidated`` lists the nodes whose rows changed: their
        precomputed ITS/alias rows are marked stale (one bitmap write
        now, no synchronous table rebuild) and every sampler's dynamic
        path — which those lanes fall back to — reads the *new* weights
        immediately.  Rows NOT listed keep serving from their
        (still-correct) tables.

        The stale rows also enter the engine's rebuild queue: subsequent
        ``run`` calls re-bake ``config.rebuild_budget`` of them per
        scheduler epoch (or call :meth:`drain_rebuilds` to repair them
        synchronously), flipping their validity bits back — the dynamic
        fallback is transient, not permanent.

        Node stats (the compiler's preprocess() output) are recomputed so
        bound/sum estimators track the new weights.  The jitted epochs
        are NOT rebuilt — the new graph/stats enter as epoch arguments
        with unchanged shapes, so a weight mutation costs no retrace.
        """
        if self.delta is not None:
            raise ValueError(
                "update_graph cannot swap graphs while a structural "
                "overlay is active; fold the pending edits with "
                "WalkEngine.compact() first, or route the change through "
                "WalkEngine.apply_updates(inserts=...) — inserting an "
                "existing edge re-weights it in place")
        if (graph.indptr.shape != self.graph.indptr.shape
                or graph.indices.shape != self.graph.indices.shape):
            raise ValueError(
                "update_graph requires unchanged topology (same "
                "indptr/indices shapes) — it is the weight-only fast "
                "path.  For structural changes use WalkEngine."
                "apply_updates(inserts=..., deletes=...), which overlays "
                "the edits under live traffic and repairs only the "
                "touched precomp rows")
        self.graph = graph
        self.stats = node_stats(graph,
                                num_labels=max(self.workload.num_labels, 1))
        if self.precomp is not None and len(np.atleast_1d(invalidated)):
            self.precomp = self.precomp.invalidate(invalidated)
            self.rebuild_queue.push(invalidated)
        self._refresh_epoch_fns()
        # the fused kernel's edge streams carry the mutated weights (and
        # the rejection kind the node-stat-derived bound table), so the
        # weight mutation re-aligns them host-side; same shapes → the
        # jitted fused epoch is reused without retrace
        self._refresh_fused_streams()

    def apply_updates(self, inserts=None, deletes=None) -> UpdateReport:
        """Apply structural edits — edge inserts and deletes — under live
        traffic, without rebuilding the engine.

        ``inserts`` is ``(src, dst, h)`` or ``(src, dst, h, labels)``
        (array-likes; inserting an existing edge re-weights it in place),
        ``deletes`` is ``(src, dst)``; deletes are applied before inserts
        within one call.  Node ids must already exist — structural
        updates never add nodes.

        The edits land in a :class:`~repro.graphs.delta.GraphDelta`
        overlay: untouched rows keep their base CSR offsets (and hence
        their per-offset RNG draws and still-valid precomp rows)
        bit-for-bit, while each touched row is re-materialised into a
        *stable* patch span, sorted by destination exactly like a fresh
        ``from_edges`` build.  The whole apply is O(touched), not O(E):
        the device overlay syncs only the dirty spans, the per-edge
        precomp tables stay in the overlay layout — valid rows are
        already addressed through the overlay's ``row_starts``, so
        :func:`~repro.core.precomp.grow_tables` merely tracks the patch
        capacity (amortized pow2 growth) while the touched rows are
        invalidated and queued for the amortized background rebuild —
        and node stats are patched for the touched rows only
        (bit-identical to a full recompute).  The one-shot O(E)
        re-layout back to the contiguous order is deferred to
        :meth:`compact` (or ``config.compact_interval``).

        A no-op edit set (nothing touched) is bit-neutral: no overlay is
        created, the mutation clock does not bump, and live schedulers
        keep their pinned views and prefetch carries.

        Reservoir/rejection fused engines keep the mega-step kernel
        while the overlay is active (the edge streams are re-aligned to
        the overlay layout, bit-identically); precomp-regime fused
        engines stand down to the staged scan until :meth:`compact`
        re-attaches the aligned table streams (``step_exec_resolved``
        reports the decision either way).
        """
        if self.delta is None:
            delta = GraphDelta(self.graph)
        else:
            delta = self.delta
        rep = delta.apply(inserts, deletes)
        if not rep.touched:
            return rep
        self.delta = delta
        self.graph = delta.materialize()
        self.stats = delta.patch_stats(self.stats, rep.touched)
        _, new_degs = delta.layout()
        # sticky pow2 pad: monotone while the overlay is active, so a
        # burst of applies reuses the jitted epoch; compact() restores
        # the exact fresh-build formula
        self._set_pad(new_degs.max(initial=0), floor=self.pad)
        if self.precomp is not None:
            self.precomp = precomp_mod.grow_tables(
                self.precomp, self.graph.num_edges).invalidate(rep.touched)
            self.rebuild_queue.push(rep.touched)
        self._refresh_epoch_fns()
        self._refresh_fused_streams()
        return rep

    def compact(self) -> int:
        """Fold the delta overlay back into a contiguous CSR (bitwise
        equal to ``from_edges`` of the mutated edge list) with one O(E)
        gather, re-laying the precomp tables from the overlay layout
        onto the new row layout — valid rows keep their values, pending
        stale rows stay queued — and restoring the fused mega-step
        (and aligned table streams) if the engine had one.  This is the
        deferred O(E) half of the apply/compact split; node stats are
        *not* recomputed — the per-row patches applied by
        :meth:`apply_updates` are bitwise equal to a fresh
        ``node_stats(graph)`` (pinned by the mutation fuzzer), so the
        carried stats are already exact.
        Returns the number of overlay rows folded (0 = no overlay)."""
        if self.delta is None:
            return 0
        folded = len(self.delta)
        old_starts, old_degs = host_row_layout(self.graph)
        graph = self.delta.compact()
        self.delta = None
        self.graph = graph
        self._set_pad(graph.max_degree())
        if self.precomp is not None:
            new_starts, new_degs = host_row_layout(graph)
            self.precomp = precomp_mod.splice_tables(
                self.precomp, old_starts, old_degs, new_starts, new_degs,
                graph.num_edges)
            # the overlay dropped the tile-aligned kernel streams; re-
            # attach them iff a resolved execution path will DMA them
            if (resolve_precomp_exec(self.config.precomp_exec) == "pallas"
                    or (self._fused_kind or "").startswith("precomp")):
                self.precomp = self.precomp.with_aligned(graph.indptr)
        self._refresh_epoch_fns()
        self._refresh_fused_streams()
        return folded

    def drain_rebuilds(self, max_rows: Optional[int] = None, *,
                       scatter: str = "donate") -> int:
        """Re-bake up to ``max_rows`` queued stale table rows right now
        (all of them when None) and flip their validity bits back.
        Returns how many rows were rebuilt.  ``run`` calls this with
        ``config.rebuild_budget`` once per scheduler epoch — the
        amortized background path, with ``scatter="copy"`` so pinned
        table views stay readable; direct calls keep the donating
        in-place scatter."""
        if self.precomp is None or not len(self.rebuild_queue):
            return 0
        self.precomp, done = self.rebuild_queue.drain(
            self.precomp, self.graph, self.workload,
            self.sampler_ctx.params, budget=max_rows, scatter=scatter)
        self.sampler_ctx = dataclasses.replace(
            self.sampler_ctx, precomp=self.precomp)
        return len(done)


def compiled_params(workload: Workload):
    # params are pure-Python hyperparameters, baked in at trace time
    return workload.params()


# ----------------------------------------------------- exact distributions
def exact_probs(graph: CSRGraph, workload: Workload, params,
                v: int, prev: int, step: int, pad: int,
                wstate=None) -> np.ndarray:
    """Ground-truth transition distribution for tests/benchmarks.

    ``wstate`` is ONE walker's program state (unbatched pytree, e.g. the
    exact visited set of the walker whose next-step distribution is being
    checked); ``None`` for stateless programs.
    """
    from repro.core.baselines import padded_weights

    ws = None
    if wstate is not None:
        ws = jax.tree_util.tree_map(lambda l: jnp.asarray(l)[None], wstate)
    w, nbr, mask = padded_weights(
        graph, workload, params,
        jnp.asarray([v], jnp.int32), jnp.asarray([prev], jnp.int32),
        jnp.asarray([step], jnp.int32), pad, ws)
    w = np.asarray(w[0])
    total = w.sum()
    p = w / total if total > 0 else w
    return p, np.asarray(nbr[0])
