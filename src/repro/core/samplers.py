"""Sampler protocol + registry — the extensibility layer of Flexi-Runtime.

Every sampling strategy the engine can run is a :class:`Sampler` object
registered by name.  The engine (`core/runtime.py`) never dispatches on
method strings: it resolves ``EngineConfig.method`` through this registry
and calls ``sampler.select(ctx, state, rng, active=live)`` once per step.
Adding a strategy therefore means registering one object here — no engine
edits; the C-SAW-style precomputed regimes (``its_precomp`` /
``alias_precomp``) and the ThunderRW-style step-interleaved pipeline
(``interleaved``) below landed exactly that way.

Architecture:

* :class:`Sampler`        — the protocol: ``select`` + capability metadata
  (:class:`SamplerCaps`: needs the compiler bound, needs full-row padding,
  supports masked partitions).
* :class:`SamplerContext` — everything static a sampler may need: graph,
  workload + params, Flexi-Compiler output, node stats, engine config,
  padding geometry; plus the bound/sum estimator evaluation helper.
* :class:`PartitionedSampler` — the paper's runtime adaptation (§4.1,
  §5.2) expressed generically: a *selector policy* splits the live lanes
  into a rejection partition and a reservoir partition, any registered
  rejection/reservoir pair executes them, and rejection lanes unresolved
  after R_max rounds fall back to the reservoir side (§7.1 soundness
  fallback).  ``adaptive`` (Eq. 11 cost model), ``erjs`` (all-rejection),
  ``random`` and ``degree`` (Fig. 13 baseline selectors) are all just
  ``PartitionedSampler`` instances with different policies.
* precomputed regime — :class:`ITSPrecompSampler` /
  :class:`AliasPrecompSampler` serve static-provable workloads from the
  baked tables of ``core/precomp.py`` (per-node invalidation bitmap gates
  every read); :class:`InterleavedSampler` pipelines the next step's
  neighbour gather behind the current move/update via the sampler-owned
  ``WalkerState.carry``.
* registry — :func:`register_sampler` / :func:`get_sampler` /
  :func:`available_samplers` (sorted).  ``runtime.METHODS`` is a snapshot
  of the registry keys taken at import; the registry itself is the source
  of truth and accepts user strategies at any time.

Sampler convention: ``select`` returns next nodes for the *active* lanes
(-1 = dead end); inactive lanes are unspecified — the engine masks them.
Telemetry (lanes served by rejection, fallback count) counts active lanes
only, so padded/dead walkers can never skew Fig. 14-style statistics.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import flexi_compiler as fc
from repro.core import precomp as precomp_mod
from repro.core.baselines import BASELINE_STEP_FNS
from repro.core.ctxutil import degrees_of, eval_weights, tile_ctx
from repro.core.erjs import erjs_step
from repro.core.ervs import (NEG_INF, _log_keys, _tile_uniforms,
                             compact_lanes, ervs_jump_step, ervs_step,
                             tile_pass)
from repro.core.types import EdgeCtx, WalkerState
from repro.graphs.csr import dist_code, search_depth


# ---------------------------------------------------------------- metadata
@dataclasses.dataclass(frozen=True)
class SamplerCaps:
    """Capability metadata the engine/scheduler can reason about."""

    needs_bound: bool = False  # evaluates the Flexi-Compiler estimators
    needs_padded_row: bool = False  # materialises [W, pad] weight rows
    supports_partition: bool = False  # honours an ``active`` lane mask
    # wants precomputed ITS/alias tables: the engine runs the is_static
    # analysis and builds core/precomp.py tables when it holds (the sampler
    # must still degrade gracefully when ctx.precomp is None).
    needs_precomp: bool = False


@dataclasses.dataclass(frozen=True)
class Estimates:
    """Per-walker Flexi-Compiler estimates (zeros when not usable)."""

    bound_max: jax.Array  # [W] upper bound of max_i w̃ (Eqs. 5–8)
    sum_est: jax.Array  # [W] estimate of Σ_i w̃ (Eq. 12)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Selection:
    """Result of one ``select`` call for a walker batch."""

    next_nodes: jax.Array  # [W] int32; -1 = dead end; inactive lanes junk
    rjs_served: jax.Array  # [] int32 — active lanes served by rejection
    fallbacks: jax.Array  # [] int32 — active lanes that hit §7.1 fallback
    # active lanes served from precomputed ITS/alias tables
    precomp_served: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # active lanes that hit a stale (invalidated) table row and took the
    # dynamic path while the row awaits its background rebuild
    stale_served: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # eRVS tile-loop trips, neighbour entries read and lane-trips (trips
    # times the lanes of the tile that ran them), summed over the
    # ervs_step / ervs_jump_step passes the sampler ran (StepStats has
    # the same fields)
    ervs_trips: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    ervs_edges: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    ervs_lane_trips: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # candidate dist searches run: eRVS lane-slots plus eRJS trials
    # (StepStats.dist_searches)
    dist_searches: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # sampler-owned cross-step state; the engine stores it in
    # WalkerState.carry for the next step (None = carry nothing)
    carry: Any = None


@dataclasses.dataclass(frozen=True)
class SamplerContext:
    """Static per-engine inputs shared by every sampler.

    Built once by ``WalkEngine``; samplers close over it inside the jitted
    epoch, so all fields are trace-time constants.
    """

    graph: Any  # CSRGraph
    workload: Any  # Workload
    params: Any  # workload.params() (static hyperparameters)
    compiled: fc.CompiledWorkload
    stats: Any  # node_stats output (h_min/h_max/h_mean per node)
    config: Any  # EngineConfig (avoid circular import with runtime)
    pad: int  # padded max degree (power of two ≥ tile)
    max_tiles: int  # ceil(pad / tile)
    # precomputed ITS/alias tables (core/precomp.py) — present only when
    # the workload is is_static-provable AND the sampler asked for them
    # (caps.needs_precomp); None otherwise.
    precomp: Optional[precomp_mod.PrecompTables] = None
    # devices the slot axis is block-sharded over: the reservoir passes
    # compact their lanes within each device's block (compact_lanes)
    shards: int = 1

    @property
    def dist_depth(self) -> int:
        """Rounds of the ``dist`` search (and of the jnp ITS search):
        ``pad`` bounds every row."""
        return search_depth(self.pad)

    def bound_inputs(self, state: WalkerState) -> fc.BoundInputs:
        vs = jnp.maximum(state.cur, 0)
        return fc.BoundInputs(
            h_min=self.stats.h_min[vs], h_max=self.stats.h_max[vs],
            h_mean=self.stats.h_mean[vs],
            deg_cur=degrees_of(self.graph, state.cur),
            deg_prev=degrees_of(self.graph, state.prev),
            cur=state.cur, prev=state.prev, step=state.step,
            # program-owned per-walker state: a concrete runtime input to
            # the synthesized estimators, like cur/prev/step
            wstate=state.wstate,
        )

    def estimates(self, state: WalkerState) -> Estimates:
        W = state.cur.shape[0]
        if not self.compiled.usable:
            z = jnp.zeros((W,), jnp.float32)
            return Estimates(bound_max=z, sum_est=z)
        bi = self.bound_inputs(state)
        _, bmax = jax.vmap(self.compiled.bound_fn)(bi)
        ssum = jax.vmap(self.compiled.sum_fn)(bi)
        return Estimates(bound_max=bmax, sum_est=ssum)


# ---------------------------------------------------------------- protocol
class Sampler(abc.ABC):
    """One sampling strategy: pick the next node for a batch of walkers."""

    name: str
    caps: SamplerCaps = SamplerCaps()

    @abc.abstractmethod
    def select(self, ctx: SamplerContext, state: WalkerState,
               rng: jax.Array, *, active: jax.Array) -> Selection:
        """Sample next nodes for lanes where ``active`` is True.

        ``rng`` is a [W] array of per-walker, per-step PRNG keys (the
        engine folds the walker's step counter into its stream key, so a
        query's randomness is independent of slot/epoch placement).
        """

    def init_carry(self, ctx: SamplerContext, num_slots: int) -> Any:
        """Initial value of the sampler's cross-step carry
        (``WalkerState.carry``).  Samplers that pipeline across steps (the
        ``interleaved`` gather-move-update pipeline) override this; the
        default carries nothing.

        Sharding contract: every array leaf of the carry must either have
        the walker-slot dim leading (``shape[0] == num_slots``) or be
        slot-free (a scalar/replicated table).  The sharded scheduler
        (docs/scaling.md) partitions exactly the leaves whose dim 0 is the
        slot dim, so a carry laid out any other way would be silently
        replicated — per-lane state must ride the ``"walkers"`` axis to
        stay on the device that owns its lane."""
        return None

    def fused_kind(self, *, usable: bool, has_precomp: bool
                   ) -> Optional[str]:
        """Which mega-step regime (``kernels/megastep_kernel.FUSED_KINDS``)
        replicates this sampler bit-for-bit, or ``None`` if the strategy
        has no fused equivalent and the engine must stay on the staged
        scan.  ``usable`` = the Flexi-Compiler synthesized estimators for
        the workload; ``has_precomp`` = baked tables exist for this run.
        The default is honest: unknown strategies are never fused."""
        return None


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, Sampler] = {}


def register_sampler(sampler: Sampler, *, overwrite: bool = False) -> Sampler:
    """Register a strategy under ``sampler.name``.  Returns it (chainable)."""
    name = sampler.name
    if not name or not isinstance(name, str):
        raise ValueError("sampler.name must be a non-empty string")
    if name in _REGISTRY and not overwrite:
        existing = _REGISTRY[name]
        raise ValueError(
            f"sampler {name!r} already registered by "
            f"{type(existing).__name__} (pass overwrite=True to replace); "
            f"registered samplers: {', '.join(available_samplers())}")
    _REGISTRY[name] = sampler
    return sampler


def get_sampler(name: str) -> Sampler:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown sampler {name!r}; registered: "
                       f"{available_samplers()}") from None


def available_samplers() -> Tuple[str, ...]:
    """Registered strategy names, **sorted** — deterministic regardless of
    import/registration order (CLI choices, error messages and docs tables
    all render the same list)."""
    return tuple(sorted(_REGISTRY))


# ------------------------------------------------------------- reservoirs
def _searches(ctx, candidates) -> jax.Array:
    """``dist`` searches of ``candidates`` edges: one each where the
    workload reads ``dist``, none otherwise."""
    if ctx.workload.needs_dist:
        return candidates
    return jnp.int32(0)


def _dense_pass(ctx, state, active, nxt) -> Selection:
    """Selection of a ``[W, tile]`` tile-loop pass over every lane."""
    trips, edges = tile_pass(ctx.graph, state.cur, active, ctx.config.tile,
                             ctx.max_tiles)
    zero = jnp.int32(0)
    lane_trips = trips * state.cur.shape[0]
    return Selection(next_nodes=nxt, rjs_served=zero, fallbacks=zero,
                     ervs_trips=trips, ervs_edges=edges,
                     ervs_lane_trips=lane_trips,
                     dist_searches=_searches(ctx,
                                             lane_trips * ctx.config.tile))


def _compacted(sampler, ctx, state, rng, active):
    """An eRVS-family ``sampler.select`` over the ``active`` lanes only,
    compacted per shard (``core/ervs.py`` ``compact_lanes``).  Returns
    (next nodes, trips, edges, lane-trips): trips and edges those of the
    dense pass over the partition, which the chunks read between them."""
    def run(lanes, on):
        sel = sampler.select(ctx, *lanes, active=on)
        return sel.next_nodes, sel.ervs_trips

    nxt, lane_trips = compact_lanes(run, (state, rng), active, ctx.shards)
    trips, edges = tile_pass(ctx.graph, state.cur, active, ctx.config.tile,
                             ctx.max_tiles)
    return nxt, trips, edges, lane_trips


class ERVSSampler(Sampler):
    """eRVS — streaming exponential-key reservoir (paper §3.2, Alg. 1)."""

    name = "ervs"
    caps = SamplerCaps(supports_partition=True)

    def select(self, ctx, state, rng, *, active):
        nxt = ervs_step(ctx.graph, ctx.workload, ctx.params,
                        state.cur, state.prev, state.step, rng,
                        tile=ctx.config.tile, max_tiles=ctx.max_tiles,
                        active=active, wstate=state.wstate)
        return _dense_pass(ctx, state, active, nxt)

    def fused_kind(self, *, usable, has_precomp):
        return "reservoir"


class ERVSJumpSampler(Sampler):
    """eRVS + A-ExpJ jumps — RNG draws only at threshold crossings."""

    name = "ervs_jump"
    caps = SamplerCaps(supports_partition=True)

    def select(self, ctx, state, rng, *, active):
        nxt = ervs_jump_step(ctx.graph, ctx.workload, ctx.params,
                             state.cur, state.prev, state.step, rng,
                             tile=ctx.config.tile, max_tiles=ctx.max_tiles,
                             active=active, wstate=state.wstate)
        return _dense_pass(ctx, state, active, nxt)


# ---------------------------------------------------------- rejection side
class RejectionComponent(abc.ABC):
    """The rejection half of a :class:`PartitionedSampler` pair."""

    @abc.abstractmethod
    def propose(self, ctx: SamplerContext, state: WalkerState,
                rng: jax.Array, bound: jax.Array, active: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Return (next_nodes [W], needs_fallback [W] bool, trials []
        int32: the candidate edges evaluated, over every lane)."""


class ERJSRejection(RejectionComponent):
    """eRJS — bound-based rejection trials (paper §3.3, Eqs. 5–8)."""

    def propose(self, ctx, state, rng, bound, active):
        nxt, fb, rounds = erjs_step(
            ctx.graph, ctx.workload, ctx.params,
            state.cur, state.prev, state.step, rng, bound=bound,
            trials_per_round=ctx.config.rjs_trials,
            max_rounds=ctx.config.rjs_max_rounds, active=active,
            wstate=state.wstate, depth=ctx.dist_depth)
        # every round runs its trials on every lane of the batch
        return nxt, fb, rounds * ctx.config.rjs_trials * state.cur.shape[0]


# -------------------------------------------------------- selector policies
# A policy maps (ctx, state, est, deg, active, rng) -> bool [W]: which of
# the active lanes should go to the rejection partition this step.
SelectorPolicy = Callable[..., jax.Array]


def cost_model_policy(ctx, state, est, deg, active, rng):
    """Eq. 11: rejection wins when ratio·max-bound < Σ-estimate."""
    return ctx.config.cost_model.prefer_rjs(est.bound_max, est.sum_est, deg)


def always_policy(ctx, state, est, deg, active, rng):
    """All-rejection (the pure ``erjs`` method); needs a usable bound."""
    W = deg.shape[0]
    if not ctx.compiled.usable:
        return jnp.zeros((W,), bool)
    return jnp.ones((W,), bool)


def random_policy(ctx, state, est, deg, active, rng):
    """Coin-flip selection (Fig. 13 baseline)."""
    coin = jax.vmap(lambda k: jax.random.bernoulli(
        jax.random.fold_in(k, 777)))(rng)
    return coin & (est.bound_max > 0)


def degree_policy(ctx, state, est, deg, active, rng):
    """Degree-threshold selection (Fig. 13 baseline): rejection for hubs."""
    return (deg >= ctx.config.degree_threshold) & (est.bound_max > 0)


SELECTOR_POLICIES: Dict[str, SelectorPolicy] = {
    "cost_model": cost_model_policy,
    "always": always_policy,
    "random": random_policy,
    "degree": degree_policy,
}


class PartitionedSampler(Sampler):
    """Runtime adaptation: policy-split lanes, compose any (rejection,
    reservoir) pair, fall back rejection→reservoir (§7.1) — and, when the
    workload is static-provable, a third *precomputed* partition served
    straight from the baked ITS tables (C-SAW's regime; O(log d) per step).

    Per-node regime order is precomp > rejection > reservoir: lanes whose
    row is eligible (valid table + ``CostModel.prefer_precomp``) never
    reach the Eq. 11 split.  The reservoir side itself can be a per-degree
    pair (``reservoir_hi``): hub lanes (degree ≥ config.jump_threshold) run
    the A-ExpJ jump reservoir, whose RNG-draw saving only amortises on long
    rows, while everyone else streams plain eRVS.

    This is the generic form of the engine's former hand-written adaptive
    path; ``adaptive``/``erjs``/``random``/``degree`` are four instances.
    """

    def __init__(self, name: str, policy: SelectorPolicy,
                 rejection: Optional[RejectionComponent] = None,
                 reservoir: Optional[Sampler] = None, *,
                 precomp_regime: bool = False,
                 reservoir_hi: Optional[Sampler] = None):
        self.name = name
        self.policy = policy
        self.rejection = rejection or ERJSRejection()
        self.reservoir = reservoir or ERVSSampler()
        self.reservoir_hi = reservoir_hi
        self.precomp_regime = precomp_regime
        self.caps = SamplerCaps(needs_bound=True, supports_partition=True,
                                needs_precomp=precomp_regime)
        for res in filter(None, [self.reservoir, self.reservoir_hi]):
            if not res.caps.supports_partition:
                raise ValueError(
                    f"reservoir {res.name!r} cannot run on a "
                    f"partition (caps.supports_partition=False)")

    def _reservoir_select(self, ctx, state, rng, deg, active):
        """Reservoir partition, optionally split by degree (hubs take the
        jump variant — the ROADMAP's per-node reservoir choice), each pass
        run on its own lanes only (:func:`_compacted`).  Returns (next
        nodes, tile-loop trips, edges read, lane-trips) summed over the
        passes."""
        if self.reservoir_hi is None:
            with jax.named_scope("ervs"):
                return _compacted(self.reservoir, ctx, state, rng, active)
        hi = active & (deg >= ctx.config.jump_threshold)
        lo = active & ~hi
        with jax.named_scope("ervs"):
            r_lo = _compacted(self.reservoir, ctx, state, rng, lo)
        with jax.named_scope("ervs_hub"):
            r_hi = _compacted(self.reservoir_hi, ctx, state, rng, hi)
        return (jnp.where(hi, r_hi[0], r_lo[0]),
                *(a + b for a, b in zip(r_lo[1:], r_hi[1:])))

    def select(self, ctx, state, rng, *, active):
        # each regime runs under a named scope (precomp, cost_model, erjs,
        # ervs, ervs_hub), so a trace maps its loops to the regime
        deg = degrees_of(ctx.graph, state.cur)
        with jax.named_scope("cost_model"):
            est = ctx.estimates(state)
        # --- third regime: static rows served from the baked tables ------
        if self.precomp_regime and ctx.precomp is not None:
            with jax.named_scope("precomp"):
                # routing discounts by the transient stale fraction: as
                # the rebuild queue backs up, fewer lanes are sent to
                # bounce off invalid rows (see CostModel.prefer_precomp)
                prefer = ctx.config.cost_model.prefer_precomp(
                    deg, frac_stale=ctx.precomp.frac_stale())
                valid = ctx.precomp.row_valid(state.cur)
                want_pre = active & valid & prefer
                stale_pre = active & ~valid & prefer
                nxt_pre = precomp_table_select(ctx, state, rng, want_pre,
                                               kind="its")
        else:
            want_pre = jnp.zeros_like(active)
            stale_pre = jnp.zeros_like(active)
            nxt_pre = jnp.full_like(state.cur, -1)
        rest = active & ~want_pre
        # --- Eq. 11 split on the remaining lanes -------------------------
        with jax.named_scope("cost_model"):
            want_rjs = self.policy(ctx, state, est, deg, rest, rng) & rest
        with jax.named_scope("erjs"):
            nxt_rjs, fb, trials = self.rejection.propose(
                ctx, state, rng, est.bound_max, want_rjs)
        # reservoir partition = lanes the policy kept + rejection fallbacks
        res_active = rest & ((~want_rjs) | fb)
        nxt_res, trips, edges, lane_trips = self._reservoir_select(
            ctx, state, rng, deg, res_active)
        nxt = jnp.where(res_active, nxt_res,
                        jnp.where(want_rjs, nxt_rjs, -1))
        nxt = jnp.where(want_pre, nxt_pre, nxt)
        # served = the regime actually produced a transition; lanes that
        # were infeasible (zero bound / all-zero weights) emit no node and
        # must not count toward Fig. 14-style coverage statistics.  A lane
        # that bounced off a stale table row counts ONLY as stale — never
        # also under the dynamic regime that absorbed it — so the regime
        # fractions partition the live lanes (telemetry mass conservation,
        # pinned by the conformance suite).
        return Selection(
            next_nodes=nxt,
            rjs_served=jnp.sum(
                (want_rjs & ~fb & (nxt_rjs >= 0)
                 & ~stale_pre).astype(jnp.int32)),
            fallbacks=jnp.sum(fb.astype(jnp.int32)),
            precomp_served=jnp.sum(
                (want_pre & (nxt_pre >= 0)).astype(jnp.int32)),
            stale_served=jnp.sum(
                (stale_pre & (nxt >= 0)).astype(jnp.int32)),
            ervs_trips=trips, ervs_edges=edges, ervs_lane_trips=lane_trips,
            dist_searches=_searches(
                ctx, lane_trips * ctx.config.tile + trials),
        )

    def fused_kind(self, *, usable, has_precomp):
        # Only the pure all-rejection composition ("erjs": always_policy
        # over the stock eRJS/eRVS pair, no degree split, no precomp
        # partition) has a mega-step replica.  With a usable bound every
        # lane runs rejection (§7.1 fallback included); without one,
        # always_policy routes every lane to the eRVS side — exactly the
        # kernel's reservoir regime.  Any custom policy/component keeps
        # the staged scan.
        structural = (self.policy is always_policy
                      and type(self.rejection) is ERJSRejection
                      and type(self.reservoir) is ERVSSampler
                      and self.reservoir_hi is None
                      and not self.precomp_regime)
        if not structural:
            return None
        return "rejection" if usable else "reservoir"


# ------------------------------------------------------- padded baselines
class PaddedRowSampler(Sampler):
    """Adapter for the §2.2 baselines (ITS / ALS / prefix-RVS / max-reduce
    RJS): they materialise one [W, pad] weight row per step — the padding
    cost the enhanced kernels avoid is part of what they measure."""

    caps = SamplerCaps(needs_padded_row=True)

    def __init__(self, name: str, step_fn: Callable, **extra_of_cfg):
        self.name = name
        self._step_fn = step_fn
        # kwargs derived from the engine config at call time, e.g.
        # trials_per_round=lambda cfg: cfg.rjs_trials
        self._extra_of_cfg = extra_of_cfg

    def select(self, ctx, state, rng, *, active):
        extra = {k: f(ctx.config) for k, f in self._extra_of_cfg.items()}
        nxt = self._step_fn(ctx.graph, ctx.workload, ctx.params,
                            state.cur, state.prev, state.step, rng,
                            pad=ctx.pad, wstate=state.wstate, **extra)
        zero = jnp.int32(0)
        return Selection(next_nodes=jnp.where(active, nxt, -1),
                         rjs_served=zero, fallbacks=zero)


# ------------------------------------------------------ precomputed regime
# Execution paths for table draws (EngineConfig.precomp_exec): the Pallas
# DMA kernels of kernels/precomp_kernel.py, or the jnp selectors of
# core/precomp.py.  Both consume the same counter-based Threefry
# (key, counter, salt) triples, so the choice never changes an output bit.
PRECOMP_EXEC_CHOICES = ("auto", "jnp", "pallas")


def resolve_precomp_exec(choice: str) -> str:
    """``auto`` → the Pallas kernels on TPU, the jnp selectors (which are
    also the interpret-mode oracles) everywhere else."""
    if choice == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return choice


def precomp_table_select(ctx: SamplerContext, state: WalkerState,
                         rng: jax.Array, active: jax.Array, *,
                         kind: str) -> jax.Array:
    """Next nodes for the ``active`` lanes straight from the baked tables
    (``kind``: "its" binary search or "alias" pick), via whichever
    execution path ``EngineConfig.precomp_exec`` resolves to.

    The "pallas" path DMAs the tile-aligned streams
    (``PrecompTables.cdf2d``/``prob2d``/``alias2d``; interpret mode when
    not on TPU) and falls back to the jnp selectors for hand-built tables
    that carry no aligned layout — a fallback with no observable effect,
    since the paths are bit-identical by construction (pinned by
    tests/test_kernels.py).
    """
    tables = ctx.precomp
    graph = ctx.graph
    exec_path = resolve_precomp_exec(ctx.config.precomp_exec)
    if exec_path == "pallas" and tables.arow0 is not None:
        # arow0 alone does not prove the per-kind value streams exist —
        # a partially-stripped table (e.g. mid-overlay) must fail loudly
        # at trace time, never DMA a missing stream into a silent wrong
        # draw.  with_aligned()/compact() re-attach the full set; or set
        # precomp_exec="jnp" to skip the kernels.
        needed = ("cdf2d",) if kind == "its" else ("prob2d", "alias2d")
        missing = [f for f in needed if getattr(tables, f) is None]
        if missing:
            raise RuntimeError(
                f"precomp_exec resolved to 'pallas' for kind={kind!r} but "
                f"the aligned table stream(s) {missing} are absent "
                f"(arow0 is attached). Re-attach via "
                f"PrecompTables.with_aligned(indptr) / engine.compact(), "
                f"or run with precomp_exec='jnp'.")
        # deferred so jnp-only engines never load the Pallas modules
        from repro.kernels import ops as kernel_ops
        from repro.kernels import precomp_kernel
        vs = jnp.maximum(state.cur, 0)
        deg = degrees_of(graph, state.cur)
        seeds = precomp_mod.threefry_seeds(rng)
        totals = tables.total[vs]
        row0 = tables.arow0[vs]
        interpret = precomp_kernel.default_interpret()
        if kind == "its":
            off = kernel_ops.its_search(tables.cdf2d, row0, deg, totals,
                                        seeds, interpret=interpret)
        else:
            off = kernel_ops.alias_pick(tables.prob2d, tables.alias2d, row0,
                                        deg, totals, seeds,
                                        interpret=interpret)
        start = graph.row_starts(vs)
        nxt = graph.indices[jnp.clip(start + jnp.maximum(off, 0), 0,
                                     graph.num_edges - 1)]
        return jnp.where(active & (off >= 0), nxt, -1)
    if kind == "its":
        return precomp_mod.its_select(
            graph, tables, state.cur, rng, active=active,
            depth=ctx.dist_depth)
    return precomp_mod.alias_select(graph, tables, state.cur, rng,
                                    active=active)


class _PrecompBase(Sampler):
    """Shared shell of the C-SAW-style precomputed samplers.

    When the engine proved the workload static, ``ctx.precomp`` holds the
    baked tables and ``select`` is a pure table lookup (Pallas kernel or
    jnp selector per ``EngineConfig.precomp_exec`` — bit-identical); lanes
    whose row was invalidated (mutated weights) take the dynamic eRVS path
    over the live graph *transiently*, counted in ``stale_served``, until
    the engine's rebuild queue re-bakes the row.  Entire runs on workloads
    that are NOT static-provable fall back to eRVS for good (not "stale" —
    there is nothing to rebuild), so the method is always sound, never
    silently stale.
    """

    caps = SamplerCaps(supports_partition=True, needs_precomp=True)
    kind = "its"  # which table family select() draws from

    def __init__(self):
        self._fallback = ERVSSampler()

    def select(self, ctx, state, rng, *, active):
        zero = jnp.int32(0)
        if ctx.precomp is None:  # workload not static-provable
            dyn = self._fallback.select(ctx, state, rng, active=active)
            return Selection(next_nodes=dyn.next_nodes, rjs_served=zero,
                             fallbacks=zero, ervs_trips=dyn.ervs_trips,
                             ervs_edges=dyn.ervs_edges,
                             ervs_lane_trips=dyn.ervs_lane_trips,
                             dist_searches=dyn.dist_searches)
        ok = active & ctx.precomp.row_valid(state.cur)
        nxt_pre = precomp_table_select(ctx, state, rng, ok, kind=self.kind)
        stale = active & ~ok
        dyn = self._fallback.select(ctx, state, rng, active=stale)
        nxt = jnp.where(ok, nxt_pre,
                        jnp.where(stale, dyn.next_nodes, -1))
        # like precomp_served, stale_served counts lanes whose (fallback)
        # draw actually produced a transition — dead-ends stay uncounted
        return Selection(
            next_nodes=nxt, rjs_served=zero, fallbacks=zero,
            precomp_served=jnp.sum((ok & (nxt_pre >= 0)).astype(jnp.int32)),
            stale_served=jnp.sum(
                (stale & (dyn.next_nodes >= 0)).astype(jnp.int32)),
            ervs_trips=dyn.ervs_trips, ervs_edges=dyn.ervs_edges,
            ervs_lane_trips=dyn.ervs_lane_trips,
            dist_searches=dyn.dist_searches)

    def fused_kind(self, *, usable, has_precomp):
        # With baked tables the kernel serves the table regime (stale rows
        # take its in-kernel reservoir fallback); without them the sampler
        # is eRVS for good, which the reservoir regime replicates.
        return f"precomp_{self.kind}" if has_precomp else "reservoir"


class ITSPrecompSampler(_PrecompBase):
    """``its_precomp`` — O(log d) binary search of the baked per-row CDF."""

    name = "its_precomp"
    kind = "its"


class AliasPrecompSampler(_PrecompBase):
    """``alias_precomp`` — O(1) draw from the baked Vose alias tables."""

    name = "alias_precomp"
    kind = "alias"


# -------------------------------------------------- step-interleaved eRVS
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PrefetchTile:
    """The ``interleaved`` sampler's cross-step carry: the first neighbour
    tile of the node each lane is *about to* occupy, gathered at the end of
    the previous step so the HBM fetch overlaps the move/update.

    All leaves lead with the walker-slot dim (the ``init_carry`` sharding
    contract), so under ``run(devices=N)`` each device carries only its own
    lanes' tiles — the prefetch never crosses the mesh: a lane's tile is
    gathered, stored and consumed on the device that owns the lane."""

    node: jax.Array  # [W] int32 — node the tile was gathered for (-1 none)
    nbr: jax.Array  # [W, tile] int32
    h: jax.Array  # [W, tile] float32
    label: jax.Array  # [W, tile] int32


class InterleavedSampler(Sampler):
    """``interleaved`` — ThunderRW-style gather-move-update pipeline.

    Identical *distribution and bit pattern* to plain eRVS (same per-tile
    counter-based uniforms, same log-key argmax), but restructured as a
    software pipeline across the engine's fused ``lax.scan`` steps: after
    selecting step t's transition, the first neighbour tile of the chosen
    node is gathered immediately (the step-t+1 *gather* overlapping the
    step-t *move/update* in the same scan body), carried in
    ``WalkerState.carry``, and consumed next step without touching HBM.

    Correctness never depends on the prefetch hitting: the carry records
    which node each tile was gathered for, and lanes whose current node
    differs (first step, scheduler refill, dead-end residue) re-fetch
    inline — a tile gathered for node v is valid for *any* lane now at v
    because graph data is immutable within a run.  Hit lanes point their
    correction-gather indices at row 0, so on hardware the prefetch
    genuinely removes the cold row fetch from the critical path.
    """

    name = "interleaved"
    caps = SamplerCaps(supports_partition=True)

    def init_carry(self, ctx, num_slots):
        tile = ctx.config.tile
        return PrefetchTile(
            node=jnp.full((num_slots,), -1, jnp.int32),
            nbr=jnp.full((num_slots, tile), -1, jnp.int32),
            h=jnp.zeros((num_slots, tile), jnp.float32),
            label=jnp.zeros((num_slots, tile), jnp.int32),
        )

    def _gather_tile0(self, ctx, node, *, cheap_lanes=None):
        """(nbr, h, label, mask) of rows ``node`` for offsets [0, tile) —
        the same values ``ctxutil.tile_ctx`` would produce.  Lanes in
        ``cheap_lanes`` read position 0 instead (their data comes from the
        prefetch; the degenerate index keeps the gather cache-hot)."""
        graph, wl = ctx.graph, ctx.workload
        tile = ctx.config.tile
        deg = degrees_of(graph, node)
        start = graph.row_starts(jnp.maximum(node, 0))
        offs = jnp.arange(tile, dtype=jnp.int32)[None, :]
        mask = (offs < deg[:, None]) & (node >= 0)[:, None]
        pos = jnp.clip(start[:, None] + offs, 0, graph.num_edges - 1)
        if cheap_lanes is not None:
            pos = jnp.where(cheap_lanes[:, None], 0, pos)
        nbr = jnp.where(mask, graph.indices[pos], -1)
        if wl.weighted:
            h = jnp.where(mask, graph.h[pos], 0.0)
        else:
            h = jnp.where(mask, 1.0, 0.0)
        if wl.needs_labels:
            label = jnp.where(mask, graph.labels[pos], -1)
        else:
            label = jnp.zeros_like(nbr)
        return nbr, h, label, mask

    def select(self, ctx, state, rng, *, active):
        graph, wl = ctx.graph, ctx.workload
        tile = ctx.config.tile
        W = state.cur.shape[0]
        cur, prev, step = state.cur, state.prev, state.step
        deg_cur = degrees_of(graph, cur)
        deg_prev = degrees_of(graph, prev)
        pf: Optional[PrefetchTile] = state.carry
        # ---- tile 0: consume the prefetch, correction-gather the misses --
        hit = (jnp.zeros((W,), bool) if pf is None
               else (pf.node == cur) & (pf.node >= 0))
        nbr_f, h_f, label_f, mask0 = self._gather_tile0(
            ctx, cur, cheap_lanes=hit if pf is not None else None)
        if pf is not None:
            nbr0 = jnp.where(hit[:, None], pf.nbr, nbr_f)
            h0 = jnp.where(hit[:, None], pf.h, h_f)
            label0 = jnp.where(hit[:, None], pf.label, label_f)
        else:
            nbr0, h0, label0 = nbr_f, h_f, label_f
        if wl.needs_dist:
            dist0 = jax.vmap(lambda p, us: jax.vmap(
                lambda u: dist_code(graph, p, jnp.maximum(u, 0),
                                    ctx.dist_depth))(us)
            )(prev, nbr0)
        else:
            dist0 = jnp.ones_like(nbr0)
        ctx0 = EdgeCtx(
            h=h0, label=label0, dist=dist0, nbr=nbr0,
            deg_cur=jnp.broadcast_to(deg_cur[:, None], (W, tile)),
            deg_prev=jnp.broadcast_to(deg_prev[:, None], (W, tile)),
            cur=jnp.broadcast_to(cur[:, None], (W, tile)),
            prev=jnp.broadcast_to(prev[:, None], (W, tile)),
            step=jnp.broadcast_to(step[:, None], (W, tile)),
        )
        w0 = eval_weights(wl, ctx.params, ctx0, mask0, state.wstate)
        u0 = _tile_uniforms(rng, 0, (W, tile))
        lk0 = jnp.where(mask0 & active[:, None], _log_keys(u0, w0), NEG_INF)
        b0 = jnp.argmax(lk0, axis=1)
        best_lk = jnp.take_along_axis(lk0, b0[:, None], axis=1)[:, 0]
        best_nbr = jnp.take_along_axis(nbr0, b0[:, None], axis=1)[:, 0]
        best_nbr = jnp.where(best_lk > NEG_INF, best_nbr, -1)
        # ---- remaining tiles: plain eRVS streaming (same math/counters) --
        deg_act = jnp.where(active, deg_cur, 0)
        # the one cross-lane op in this sampler: a max over (possibly
        # device-sharded) lanes, which GSPMD lowers to an all-reduce — an
        # order-free reduction, so the trip count (and every bit of the
        # output) matches the single-device run.
        needed = (jnp.max(deg_act) + tile - 1) // tile
        needed = jnp.minimum(needed, ctx.max_tiles)

        def body(t, carry):
            best_lk, best_nbr = carry
            tctx, tmask = tile_ctx(graph, wl, cur, prev, step,
                                   jnp.full((W,), t * tile, jnp.int32), tile,
                                   depth=ctx.dist_depth)
            w = eval_weights(wl, ctx.params, tctx, tmask, state.wstate)
            u = _tile_uniforms(rng, t, (W, tile))
            lk = jnp.where(tmask & active[:, None], _log_keys(u, w), NEG_INF)
            tb = jnp.argmax(lk, axis=1)
            tile_lk = jnp.take_along_axis(lk, tb[:, None], axis=1)[:, 0]
            tile_nbr = jnp.take_along_axis(tctx.nbr, tb[:, None], axis=1)[:, 0]
            upd = tile_lk > best_lk
            return (jnp.where(upd, tile_lk, best_lk),
                    jnp.where(upd, tile_nbr, best_nbr))

        best_lk, best_nbr = jax.lax.fori_loop(1, needed, body,
                                              (best_lk, best_nbr))
        nxt = jnp.where(active, best_nbr, -1)
        # ---- prefetch for step t+1: gather the chosen node's first tile --
        nxt_node = jnp.where(active & (nxt >= 0), nxt, -1)
        pn_nbr, pn_h, pn_label, _ = self._gather_tile0(ctx, nxt_node)
        carry = PrefetchTile(node=nxt_node, nbr=pn_nbr, h=pn_h,
                             label=pn_label)
        zero = jnp.int32(0)
        # tile 0 runs whatever the trip count
        tiles = jnp.maximum(needed, 1).astype(jnp.int32)
        return Selection(next_nodes=nxt, rjs_served=zero, fallbacks=zero,
                         dist_searches=_searches(ctx, tiles * W * tile),
                         carry=carry)


# --------------------------------------------------------------- built-ins
# NOTE: runtime.METHODS snapshots available_samplers() at import — a sorted
# tuple, so registration order here carries no external meaning.
register_sampler(PartitionedSampler("adaptive", cost_model_policy,
                                    precomp_regime=True,
                                    reservoir_hi=ERVSJumpSampler()))
register_sampler(ERVSSampler())
register_sampler(ERVSJumpSampler())
register_sampler(PartitionedSampler("erjs", always_policy))
_BASELINE_CFG_KW = {
    "rjs_maxreduce": dict(trials_per_round=lambda cfg: cfg.rjs_trials,
                          max_rounds=lambda cfg: 4 * cfg.rjs_max_rounds),
}
for _name, _fn in BASELINE_STEP_FNS.items():
    register_sampler(PaddedRowSampler(_name, _fn,
                                      **_BASELINE_CFG_KW.get(_name, {})))
register_sampler(PartitionedSampler("random", random_policy))
register_sampler(PartitionedSampler("degree", degree_policy))
register_sampler(ITSPrecompSampler())
register_sampler(AliasPrecompSampler())
register_sampler(InterleavedSampler())
