"""Logical-axis sharding: the one place that knows how tensors map to mesh.

Model code annotates tensors with *logical* axis names ("batch", "heads",
"mlp", "experts", …) via :func:`shard`.  A :class:`MeshRules` context maps
logical names to mesh axes with **automatic divisibility fallback**: a mesh
axis that does not evenly divide the tensor dimension is dropped from the
spec (e.g. yi-6b's 4 KV heads on a 16-way model axis → replicated KV while
Q stays tensor-parallel).  Outside any context, annotations are no-ops, so
smoke tests and single-host runs never touch device state.

Parameter sharding is name-based: every parameter leaf name has a logical
signature in :data:`LEAF_LOGICAL`; :func:`param_specs` walks a params
pytree and emits a matching PartitionSpec pytree (consumed by pjit
in_shardings and by the checkpoint resharder).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# ----------------------------------------------------------------- rules

#: logical axis -> tuple of mesh axes (order matters; composite allowed)
DEFAULT_LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),       # DP over pod × data
    "seq": (),                       # replicated by default; SP opt-in
    "embed": (),                     # d_model — FSDP shards it over "data"
    "heads": ("model",),             # TP
    "kv_heads": ("model",),          # TP (falls back when indivisible)
    "mlp": ("model",),               # TP
    "experts": ("model",),           # EP
    "vocab": ("model",),             # TP on vocab dim
    "kv_seq": ("model",),            # decode KV-cache context parallelism
    "capacity": (),
    "state": (),
    "conv": (),
    "qk_depth": (),
    # walk-engine slot pool: the leading dim of every WalkerState leaf
    # (see repro.core.types.WalkerState.BATCH_AXIS) shards over a 1D
    # walker mesh.  Lanes are independent, so this is pure data
    # parallelism; the graph stays replicated per device.
    "walkers": ("walkers",),
}

FSDP_RULES = dict(DEFAULT_LOGICAL_RULES, embed=("pod", "data"))
# sequence-parallel long-context rules: shard sequence over data axis
SP_RULES = dict(DEFAULT_LOGICAL_RULES, seq=("data",))


@dataclasses.dataclass(frozen=True)
class MeshRules:
    mesh: Mesh
    logical: Dict[str, Tuple[str, ...]]
    # explicit bf16 tensor-parallel reductions (shard_map psum) for the
    # attention-out / MLP-down projections — halves the TP wire bytes vs
    # the fp32 all-reduce GSPMD otherwise emits (§Perf iteration B2)
    tp_bf16_reduce: bool = False

    def axis_size(self, names: Tuple[str, ...]) -> int:
        n = 1
        for a in names:
            n *= self.mesh.shape[a]
        return n


_STATE = threading.local()


def current_rules() -> Optional[MeshRules]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def activation_sharding_ctx(mesh: Mesh, logical: Optional[Dict] = None,
                            fsdp: bool = False, seqpar: bool = False,
                            tp_bf16_reduce: bool = False):
    base = FSDP_RULES if fsdp else DEFAULT_LOGICAL_RULES
    if seqpar:
        base = dict(base, seq=("data",))
    logical = dict(base, **(logical or {}))
    # drop mesh axes the mesh does not actually have (single-pod meshes)
    have = set(mesh.axis_names)
    logical = {k: tuple(a for a in v if a in have) for k, v in logical.items()}
    prev = current_rules()
    _STATE.rules = MeshRules(mesh=mesh, logical=logical,
                             tp_bf16_reduce=tp_bf16_reduce)
    try:
        yield _STATE.rules
    finally:
        _STATE.rules = prev


# ------------------------------------------------------- walker slot pool
#
# The streaming epoch scheduler (repro.core.runtime) shards its fixed pool
# of walker slots over a 1D mesh: each device owns a contiguous block of
# slots, the single host-side refill queue feeds them round-robin, and the
# graph is replicated.  Because every lane's RNG stream is keyed per
# *query* (never per slot or device), results are bit-identical for any
# device count — sharding only changes where a lane's arithmetic runs.

def walker_mesh(num_devices: Optional[int] = None) -> Mesh:
    """A 1D mesh over ``num_devices`` (default: all local devices) whose
    single axis is named ``"walkers"`` — the axis ``DEFAULT_LOGICAL_RULES``
    maps the slot-pool batch dim onto.

    The axis is ``Auto`` (GSPMD-propagated): the scheduler's host refills
    (``.at[idx].set`` on sharded leaves) and other eager ops must accept
    walker-sharded operands without entering a mesh context, which
    ``Explicit`` axes (``jax.make_mesh``'s default) refuse."""
    devs = jax.devices()
    n = len(devs) if num_devices is None else int(num_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"num_devices must be in [1, {len(devs)}], got {n}")
    return jax.make_mesh((n,), ("walkers",), devices=devs[:n],
                         axis_types=(AxisType.Auto,))


def replicate(tree, mesh: Mesh):
    """Place every array leaf of ``tree`` (graph, node stats, precomp
    tables) on every device of ``mesh`` — once, so sharded epochs read a
    local copy instead of pulling operands from device 0."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, rep)
        if isinstance(leaf, jax.Array) else leaf, tree)


def walker_rules(mesh: Mesh) -> MeshRules:
    """MeshRules exposing only the walker axis (engine-internal; model
    activations never see it)."""
    return MeshRules(mesh=mesh, logical={"walkers": ("walkers",)})


def walker_spec(leaf: jax.Array, num_slots: int, mesh: Mesh) -> P:
    """PartitionSpec for one slot-pool pytree leaf: dim 0 shards over the
    walker axis iff it is the slot dim (``shape[0] == num_slots``); every
    other dim — and slot-count-free leaves, e.g. a scalar carry — stays
    replicated.  Divisibility fallback applies: a pool that does not
    divide the mesh is replicated rather than mis-sharded (the engine
    pads the pool so this never triggers in practice)."""
    shape = jnp.shape(leaf)
    if not shape or shape[0] != num_slots:
        return P()
    axes = ("walkers",) + (None,) * (len(shape) - 1)
    return logical_to_spec(axes, shape, walker_rules(mesh))


def shard_walker_state(state, num_slots: int, mesh: Mesh):
    """Place every leaf of a WalkerState (or any slot-pool pytree) on the
    walker mesh.  Leaves already laid out correctly are untouched
    (``device_put`` with an equal sharding is a no-op), so the scheduler
    can cheaply re-assert the layout after each host-side refill."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(
            leaf, NamedSharding(mesh, walker_spec(leaf, num_slots, mesh))),
        state)


def tp_down_proj(x: jax.Array, w: jax.Array) -> jax.Array:
    """Down-projection x @ w with the contraction dim tensor-parallel.

    Default: plain matmul (GSPMD inserts the all-reduce — observed at
    fp32 on partial products, 2× the necessary wire bytes).  With
    ``tp_bf16_reduce``: shard_map with an explicit bf16 psum over the
    model axis — the standard production trick of reducing activations
    at their storage dtype.
    """
    rules = current_rules()
    if rules is None or not rules.tp_bf16_reduce:
        return x @ w
    mesh = rules.mesh
    if "model" not in mesh.axis_names or \
            x.shape[-1] % mesh.shape["model"] != 0:
        return x @ w
    from jax.experimental.shard_map import shard_map

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    x_spec = P(batch_axes if len(batch_axes) > 1 else
               (batch_axes[0] if batch_axes else None),
               *([None] * (x.ndim - 2)), "model")
    w_spec = P("model", None)
    out_spec = P(x_spec[0], *([None] * (x.ndim - 1)))

    def local(xl, wl):
        part = (xl @ wl).astype(x.dtype)  # reduce at bf16, not fp32
        return jax.lax.psum(part, "model")

    return shard_map(local, mesh=mesh, in_specs=(x_spec, w_spec),
                     out_specs=out_spec)(x, w)


def logical_to_spec(logical_axes: Tuple[Optional[str], ...],
                    shape: Tuple[int, ...],
                    rules: Optional[MeshRules] = None) -> P:
    """PartitionSpec for a tensor, with divisibility fallback per dim."""
    rules = rules or current_rules()
    if rules is None:
        return P()
    out = []
    used = set()
    for dim, name in zip(shape, logical_axes):
        if name is None or name not in rules.logical:
            out.append(None)
            continue
        axes = tuple(a for a in rules.logical[name] if a not in used)
        if not axes:
            out.append(None)
            continue
        size = 1
        kept = []
        for a in axes:
            if dim % (size * rules.mesh.shape[a]) == 0:
                kept.append(a)
                size *= rules.mesh.shape[a]
        if not kept:
            out.append(None)
        else:
            used.update(kept)
            out.append(tuple(kept) if len(kept) > 1 else kept[0])
    return P(*out)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op outside a context).

    If the rules define "embed_act", activations asking for "embed" get it
    instead — this splits the parameter d_model sharding (e.g. ZeRO-3
    weight-gathered inference shards params 256-way) from the activation
    residual-stream sharding (replicated on D in that layout).
    """
    rules = current_rules()
    if rules is None:
        return x
    axes = tuple(("embed_act" if (a == "embed" and
                                  "embed_act" in rules.logical) else a)
                 for a in logical_axes)
    spec = logical_to_spec(axes, x.shape, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# ------------------------------------------------------- parameter rules

#: parameter leaf name -> logical axes per dim (rank must match)
LEAF_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / head.  The token-embedding table shards the VOCAB dim:
    # GSPMD partitions the lookup as masked-local-gather + all-reduce of
    # the [B,S,D] result (cheap).  Sharding d_model instead trips an SPMD
    # partitioner bug on multi-segment models (invalid reshard slice,
    # observed on the 16×16 mesh).  The LM head shards the vocab dim
    # (Megatron-style); its d_model contraction stays local.
    "embed": ("vocab", None),
    "lm_head": (None, "vocab"),
    # attention
    "wq": ("embed", "heads"),
    "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"),
    "wo": ("heads", "embed"),
    "q_norm": ("qk_depth",),
    "k_norm": ("qk_depth",),
    # dense mlp
    "wi": ("embed", "mlp"),
    "wg": ("embed", "mlp"),
    "wd": ("mlp", "embed"),
    # MoE
    "router": ("embed", "experts"),
    "we_i": ("experts", "embed", "mlp"),
    "we_g": ("experts", "embed", "mlp"),
    "we_d": ("experts", "mlp", "embed"),
    "ws_i": ("embed", "mlp"),
    "ws_g": ("embed", "mlp"),
    "ws_d": ("mlp", "embed"),
    # norms
    "norm1": ("embed",),
    "norm2": ("embed",),
    "final_norm": ("embed",),
    "norm": ("embed",),
    # RG-LRU recurrent block
    "rg_in": ("embed", "mlp"),
    "rg_gate": ("embed", "mlp"),
    "rg_out": ("mlp", "embed"),
    "rg_conv": ("conv", "mlp"),
    "rg_a": ("mlp",),
    "rg_input_gate": ("mlp", "conv"),
    "rg_a_gate": ("mlp", "conv"),
    # Mamba2
    "m_in": ("embed", "mlp"),
    "m_conv": ("conv", "mlp"),
    "m_alog": ("state",),
    "m_d": ("state",),
    "m_norm": ("mlp",),
    "m_out": ("mlp", "embed"),
    "m_dtbias": ("state",),
}


def param_specs(params, rules: Optional[MeshRules] = None):
    """PartitionSpec pytree for a params pytree (name-based; stacked layer
    dims — leading dims beyond the leaf signature — are replicated)."""
    rules = rules or current_rules()

    def spec_of(path, leaf):
        name = None
        for entry in reversed(path):
            key = getattr(entry, "key", None) or getattr(entry, "name", None)
            if isinstance(key, str) and key in LEAF_LOGICAL:
                name = key
                break
        if name is None:
            return P()
        logical = LEAF_LOGICAL[name]
        rank = len(leaf.shape)
        # stacked-layer leading dims (scan over layers) -> None
        pad = (None,) * (rank - len(logical))
        axes = pad + logical
        if rules is None:
            return P(*([None] * rank))
        return logical_to_spec(axes, leaf.shape, rules)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def named_shardings(params, mesh: Mesh, rules: Optional[MeshRules] = None):
    specs = param_specs(params, rules)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)
