"""Shared types of the FlexiWalker core: edge contexts, walk programs,
walker state.

The user-facing programming model is the composable **walk program**
(the paper's gather-move-update API of §4.2, extended to per-walker
state): a :class:`WalkProgram` supplies

  * ``init()``              → hyperparameters (pytree of scalars/arrays),
  * ``init_walker_state(q)`` → arbitrary per-walker state pytree (or None),
  * ``get_weight(ctx, params, wstate)`` → transition weight w̃ of ONE edge,
  * ``on_step(ctx, params, wstate) → wstate``   (post-selection update),
  * ``should_stop(ctx, params, wstate) → bool`` (early termination).

``get_weight`` must be jax-traceable on scalar inputs; the engine vmaps it
over [walkers × neighbor-tile] blocks, and Flexi-Compiler abstract-interprets
its jaxpr to synthesise the max/sum estimators (see flexi_compiler.py).
:class:`Workload` — the original bare ``get_weight(ctx, params)`` protocol
— survives as a deprecated thin subclass; :func:`from_workload` is the
zero-cost adapter (the wrapped jaxpr is identical, so paths and telemetry
are bit-identical through it).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeCtx:
    """Context for one candidate edge (v_cur → nbr).  All scalars.

    Fields split into two provenance classes, which is what the compiler's
    flag allocator reasons about:

    per-edge (abstract at compile time, indexed at runtime):
        h      — edge property weight h(v, u)
        label  — edge label (MetaPath)
        dist   — Node2Vec distance code dist(v', u) ∈ {0, 1, 2}
        nbr    — neighbour node id u
    per-node / per-step (concrete scalars at bound-evaluation time):
        deg_cur, deg_prev — d(v), d(v')
        cur, prev         — node ids v, v'
        step              — walk step index
    """

    h: jax.Array
    label: jax.Array
    dist: jax.Array
    nbr: jax.Array
    deg_cur: jax.Array
    deg_prev: jax.Array
    cur: jax.Array
    prev: jax.Array
    step: jax.Array


# Field taxonomy used by Flexi-Compiler (paper Fig. 9c flag allocator).
EDGE_FIELDS = ("h", "label", "dist", "nbr")
NODE_FIELDS = ("deg_cur", "deg_prev", "cur", "prev", "step")
# Enumerable per-edge fields and their domains (for the Eq. 12 sum helper).
ENUM_DOMAINS = {"dist": (0, 1, 2)}


def _stateless(query):
    """Default ``init_walker_state``: the program carries no per-walker
    state (``wstate`` is the empty pytree ``None`` everywhere)."""
    return None


@dataclasses.dataclass(frozen=True)
class WalkProgram:
    """A composable dynamic-walk program (the framework's primary contract).

    The walk *program* — not just the edge weight — is the unit of user
    extension: per-walker state, step hooks and early termination compose
    with every registered sampler and the streaming scheduler with zero
    engine edits.

    Callable fields
    ---------------
    ``init()``
        Hyperparameters (``params``), baked in at trace time.  Must be
        hashable (frozen dataclasses / tuples), like before.
    ``init_walker_state(query)``
        Per-walker state pytree for the walker serving query id ``query``
        (an int32 scalar, traced under vmap).  Return ``None`` (the
        default) for stateless programs.  Leaves may be any shape/dtype;
        the engine batches them with a leading walker-slot dim, so under
        ``run(devices=N)`` each device carries only its own lanes' state
        (the ``WalkerState`` sharding contract).
    ``get_weight(ctx, params, wstate)``
        Transition weight w̃ ≥ 0 of ONE candidate edge.  ``wstate`` is the
        walker's CURRENT state (the value most recently returned by
        ``on_step``); it is a per-walker runtime input to the Flexi-
        Compiler's bound analysis, exactly like ``cur``/``prev``/``step``.
    ``on_step(ctx, params, wstate) -> wstate``
        Post-selection state transition, applied only to lanes that
        actually moved.  ``None`` (default) leaves ``wstate`` untouched.
    ``should_stop(ctx, params, wstate) -> bool``
        Early termination, evaluated right after ``on_step`` with the NEW
        state.  A True verdict folds into the slot ``alive`` mask: the
        walker emits no further path entries, stops counting toward
        telemetry, and its scheduler slot is refilled at the next epoch
        boundary.  ``None`` (default) walks the full ``walk_len``.

    Transition-context contract (``on_step`` / ``should_stop``)
    -----------------------------------------------------------
    Both hooks receive one per-walker :class:`EdgeCtx` describing the
    transition just taken: ``nbr`` = the node moved to, ``cur``/``prev`` =
    the nodes departed (pre-move), ``step`` = the 0-based index of the
    step just taken, ``deg_cur``/``deg_prev`` = degrees of ``cur``/
    ``prev``.  The per-edge payload fields are NOT resolved for the chosen
    edge (``h=1``, ``label=-1``, ``dist=-1``): recovering them would cost
    a row search per step, and no shipped program needs them — derive what
    you need from ``nbr`` and your own state instead.
    """

    name: str
    init: Callable[[], Any]
    get_weight: Callable[[EdgeCtx, Any, Any], jax.Array]
    init_walker_state: Callable[[jax.Array], Any] = _stateless
    on_step: Optional[Callable[[EdgeCtx, Any, Any], Any]] = None
    should_stop: Optional[Callable[[EdgeCtx, Any, Any], jax.Array]] = None
    needs_dist: bool = False  # dist(v',u) is expensive; only compute on demand
    needs_labels: bool = False
    num_labels: int = 1
    weighted: bool = True  # whether ctx.h participates (paper's (un)weighted)
    walk_len: int = 80  # paper default: 80 steps (5 for MetaPath)

    def params(self):
        return self.init()

    # Single indirection every internal weight evaluation goes through —
    # the legacy ``Workload`` subclass overrides it to drop ``wstate``, so
    # kernels never sniff signatures.
    def edge_weight(self, ctx: EdgeCtx, params, wstate) -> jax.Array:
        return self.get_weight(ctx, params, wstate)

    @property
    def has_hooks(self) -> bool:
        """Whether the engine must run the per-step hook machinery."""
        return self.on_step is not None or self.should_stop is not None

    def wstate_template(self) -> Any:
        """One walker's initial state as concrete arrays (trace template)."""
        return jax.tree_util.tree_map(
            jnp.asarray, self.init_walker_state(jnp.int32(0)))

    def init_wstate_batch(self, query_ids: jax.Array) -> Any:
        """Per-walker state for a batch of query ids ([W]-leading leaves)."""
        return jax.vmap(self.init_walker_state)(
            jnp.asarray(query_ids, jnp.int32))


@dataclasses.dataclass(frozen=True)
class Workload(WalkProgram):
    """DEPRECATED — the original bare protocol (``get_weight(ctx, params)``
    + flags).  Still constructible; adapts transparently into the
    :class:`WalkProgram` contract with bit-identical paths/telemetry (the
    wrapped weight function traces to the same jaxpr).  New code should
    construct :class:`WalkProgram` directly."""

    def __post_init__(self):
        warnings.warn(
            "Workload is deprecated; define a WalkProgram instead "
            "(get_weight takes (ctx, params, wstate), and per-walker "
            "state / on_step / should_stop become available)",
            DeprecationWarning, stacklevel=3)

    def edge_weight(self, ctx: EdgeCtx, params, wstate) -> jax.Array:
        return self.get_weight(ctx, params)  # legacy two-arg signature


def from_workload(workload) -> WalkProgram:
    """Zero-cost adapter: any legacy workload object (a :class:`Workload`
    or anything with its attributes) as a :class:`WalkProgram`.

    The returned program's ``get_weight`` simply drops the (empty)
    ``wstate`` argument, so it traces to the *identical jaxpr* — paths,
    telemetry and compiler analysis are bit-identical to the legacy path.
    """
    if isinstance(workload, WalkProgram) and not isinstance(workload, Workload):
        return workload  # already speaks the new protocol
    legacy_gw = workload.get_weight
    return WalkProgram(
        name=workload.name,
        init=workload.init,
        get_weight=lambda ctx, params, wstate: legacy_gw(ctx, params),
        needs_dist=workload.needs_dist,
        needs_labels=workload.needs_labels,
        num_labels=workload.num_labels,
        weighted=workload.weighted,
        walk_len=workload.walk_len,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WalkerState:
    """State of a batch of W walker *slots* (a pytree; leading dim W).

    This is the carry of the engine's ``lax.scan`` step loop and the unit
    the streaming epoch scheduler refills: a slot whose walker finished is
    overwritten host-side with the next queued query (``alive`` stays False
    for empty slots, so they are masked out of kernels and telemetry).

    ``rng`` holds *raw key data* (``jax.random.key_data`` of a per-query
    fold of the run key) rather than typed key arrays so slots can be
    refilled with plain ``.at[idx].set`` updates; the engine re-wraps it
    with ``jax.random.wrap_key_data`` and folds in ``step`` each step, so a
    query's random stream is independent of slot/epoch placement.

    Field invariants (what pad/dead lanes may contain)
    --------------------------------------------------
    * A lane is **live** for a step iff ``alive ∧ degree(cur) > 0 ∧
      step < num_steps``.  Only live lanes sample, emit path entries, or
      count toward telemetry.
    * ``alive == False`` marks an *empty slot* (never filled, or already
      drained) **or** a dead-ended walk.  Every other field of such a lane
      is unspecified residue: ``cur``/``prev`` keep whatever the previous
      occupant (or the zero-init) left, ``step`` may be ≥ num_steps, and
      ``rng`` may be a stale stream.  Correctness never depends on them —
      samplers receive the live mask via ``active`` and must treat masked
      lanes' outputs as junk (the engine re-masks with -1 regardless).
    * ``cur`` is always a valid node id (≥ 0) for lanes that have ever been
      occupied; ``prev`` is -1 until the occupant's first transition.
    * ``step`` counts transitions taken by the *current occupant only*; the
      scheduler resets it to 0 on refill, so path indexing (``step + 1``)
      is per-query, not per-slot.
    * ``carry`` is sampler-owned cross-step state (e.g. the ``interleaved``
      sampler's prefetched neighbour tile).  The engine threads it through
      the scan and across epochs untouched, and it must never influence a
      lane's *distribution* — only how data is fetched.  Refills do NOT
      reset it: samplers must validate it per lane (the prefetch tile
      records which node it was gathered for and is re-fetched on
      mismatch).  ``None`` for samplers that carry nothing.
    * ``wstate`` is **program-owned** per-walker state (the ``WalkProgram``
      contract): every leaf is slot-dim-leading, advanced only by
      ``on_step`` on lanes that moved, and — unlike ``carry`` — refills DO
      reset it (a refilled slot gets ``init_walker_state(query)``, so a
      query's state, like its RNG stream, is independent of slot/epoch/
      device placement).  Dead/pad lanes hold residue the live mask hides.
      ``None`` for stateless programs.

    Sharding (docs/scaling.md)
    --------------------------
    Dim 0 of every leaf is the slot dim; its logical axis name is
    :data:`BATCH_AXIS` (``"walkers"``), which the walker mesh rules in
    ``repro.distributed.sharding`` map onto a 1D device mesh.  Lanes never
    read each other's state (the only cross-lane ops in the engine are
    telemetry sums and the tile-trip ``max``, both order-insensitive
    reductions), so sharding the slot dim changes *where* a lane computes
    but never *what* it computes — the scheduler's batch-invariance
    contract extends to topology invariance.  Carry leaves must keep the
    slot dim leading for the same reason (see ``Sampler.init_carry``).
    """

    #: logical axis name of dim 0 of every leaf (the walker-slot dim)
    BATCH_AXIS = "walkers"

    cur: jax.Array  # [W] int32 current node
    prev: jax.Array  # [W] int32 previous node (-1 before the first step)
    step: jax.Array  # [W] int32 steps taken by the current occupant
    alive: jax.Array  # [W] bool — False for empty slots and dead-ended walks
    rng: jax.Array  # [W, key_size] uint32 raw per-walker key data
    carry: Any = None  # sampler-owned pytree (see invariants above)
    wstate: Any = None  # program-owned pytree (see invariants above)

    @staticmethod
    def stream_key_data(key: jax.Array, ids: jax.Array) -> jax.Array:
        """Raw key data of the per-query streams fold_in(key, id).

        The single source of the stream derivation: ``create`` (slot i =
        query i) and the engine's refill queue (arbitrary query→slot
        placement) must use the same expression for ``run``/``walk_batch``
        bit-compatibility.
        """
        return jax.vmap(lambda i: jax.random.key_data(
            jax.random.fold_in(key, i)))(ids.astype(jnp.int32))

    @staticmethod
    def create(starts: jax.Array, key: jax.Array,
               wstate: Any = None) -> "WalkerState":
        """A fully-occupied batch: walker i gets stream fold_in(key, i)
        (and, when ``wstate`` is given, the program state for query i)."""
        W = starts.shape[0]
        rng = WalkerState.stream_key_data(key, jnp.arange(W, dtype=jnp.int32))
        return WalkerState(
            cur=starts.astype(jnp.int32),
            prev=jnp.full((W,), -1, jnp.int32),
            alive=jnp.ones((W,), bool),
            step=jnp.zeros((W,), jnp.int32),
            rng=rng,
            wstate=wstate,
        )

    def stream_keys(self) -> jax.Array:
        """[W] typed per-step keys: the walker's stream ⊕ its step count."""
        return jax.vmap(lambda kd, s: jax.random.fold_in(
            jax.random.wrap_key_data(kd), s))(self.rng, self.step)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepStats:
    """Per-step telemetry (a pytree, stacked by the epoch scan).

    All counters cover *live* lanes only — padded/empty slots and finished
    walkers never contribute (Fig. 14 statistics stay unbiased under the
    streaming scheduler's partial epochs).
    """

    #: bit positions of the per-(lane, step) flag words the fused
    #: mega-step kernel emits (kernels/megastep_kernel.py); plain class
    #: attributes, not dataclass fields
    LIVE, RJS, FALLBACK, PRECOMP, STALE = 0, 1, 2, 3, 4

    live: jax.Array  # [] int32 — walkers that attempted this step
    rjs_served: jax.Array  # [] int32 — lanes served by rejection sampling
    fallbacks: jax.Array  # [] int32 — §7.1 rejection→reservoir fallbacks
    # lanes served from precomputed ITS/alias tables (the static regime)
    precomp_served: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # lanes that would have been table-served but hit a stale (invalidated)
    # row and took the dynamic path instead — transient while the rebuild
    # queue drains; 0 once every stale row has been re-baked
    stale_served: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # staged-path work counters, not lane counts: eRVS tile-loop trips
    # this step, summed over the reservoir passes, the neighbour entries
    # those trips read (core/ervs.py:tile_pass), and the lane-trips run:
    # each loop's trips times the lanes of its tile (all slots for a
    # dense pass, shards · K for a compacted chunk, core/ervs.py:
    # compact_lanes).  ervs_lane_trips / (ervs_trips · slots) is the share
    # of the dense passes' lane-trips still run, ervs_edges /
    # (ervs_lane_trips · tile) the loops' useful fraction of lane-slots.
    # The fused mega-step runs its own per-lane loops and reports 0.
    ervs_trips: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    ervs_edges: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    ervs_lane_trips: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    # candidate dist searches run, for workloads that read dist (0
    # otherwise): the eRVS lane-slots (ervs_lane_trips · tile, the
    # interleaved sampler's tile loop likewise) plus the eRJS trials
    # (rounds · trials per round · slots).  Times
    # WalkEngine.dist_depth, the gather rounds the search costs.  The
    # padded-row baselines and the fused mega-step report 0.
    dist_searches: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))

    def host_totals(self) -> dict:
        """Each counter summed to a host int, keyed by field name.

        The single epoch-boundary reduction both the engine's ``run`` loop
        and the serving scheduler use to accumulate telemetry: integer
        sums are order-free exact, so host-side accumulation across epochs
        is bit-identical to a single fused reduction.
        """
        return {f.name: int(np.asarray(getattr(self, f.name)).sum())
                for f in dataclasses.fields(self)}

    @classmethod
    def from_flag_bits(cls, flags: jax.Array) -> "StepStats":
        """Reduce a [W, T] int32 flag-bit matrix to per-step counters
        ([T]-leaf StepStats, the same pytree the staged epoch scan
        stacks).  Integer sums per bit, so the reduction is order-free
        exact — fused and staged lane counters match bit for bit; the
        staged-only tile-loop counters read 0."""
        def count(bit):
            return jnp.sum((flags >> bit) & 1, axis=0, dtype=jnp.int32)

        live = count(cls.LIVE)
        zero = jnp.zeros_like(live)
        return cls(live=live, rjs_served=count(cls.RJS),
                   fallbacks=count(cls.FALLBACK),
                   precomp_served=count(cls.PRECOMP),
                   stale_served=count(cls.STALE),
                   ervs_trips=zero, ervs_edges=zero, ervs_lane_trips=zero,
                   dist_searches=zero)
