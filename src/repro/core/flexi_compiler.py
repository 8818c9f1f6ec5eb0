"""Flexi-Compiler (paper §4.2) — compile-time analysis of user walk logic.

The paper statically analyses the user's CUDA ``get_weight`` with
Clang/LLVM (AST + IR dataflow) and *generates source* for three artefacts:

  preprocess()        — per-node max/sum pointers for indexed arrays (h_MAX…)
  get_weight_max()    — a cheap upper bound of max_u w̃(v, u)   (feeds eRJS)
  get_weight_sum()    — an estimate of Σ_u w̃(v, u) via Eq. 12  (feeds Eq. 11)

JAX adaptation: user workloads are jax-traceable, so "the IR" is the jaxpr.
We run two abstract interpretations over it:

1. **Interval arithmetic** (the max helper): every value carries
   [lo, hi] endpoints — *runtime* scalars, so the synthesized bound function
   is itself jittable and evaluated per walker per step.  Per-edge fields
   (h, label, dist, nbr) enter as intervals (h's from the preprocessed
   per-node stats — the generated ``preprocess()``); node/step fields enter
   exact (lo == hi) because the runtime knows v, v', step.  The output's
   ``hi`` IS ``get_weight_max()``.  For factorable code like Node2Vec this
   reproduces the paper's max(w)·max(h) bound exactly; for non-factorable
   code it stays sound where the paper's pattern-matching would bail.

2. **Provenance/taint** (the flag allocator): each interval's *endpoints*
   carry the set of runtime-varying inputs they depend on.  Output taint ⊆ ∅
   ⇒ PER_KERNEL (one bound for the whole launch, e.g. unweighted Node2Vec);
   anything node/step-dependent ⇒ PER_STEP — the paper's exact flag lattice.

3. **Soundness fallback** (§7.1): any primitive outside the abstract domain
   (data-dependent loops, scatter, sort, PRNG…) ⇒ FALLBACK: the engine runs
   eRVS-only, and a warning names the offending primitive.

The sum helper implements Eq. 12 by *enumeration*: evaluate get_weight over
the small declared domains (dist ∈ {0,1,2}, label ∈ [0, L)) with h replaced
by its per-node mean, and average.  (The paper averages unique branch return
values; domain-uniform averaging is equivalent for Node2Vec and strictly
more accurate for MetaPath — recorded as a deviation in DESIGN.md.)
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from repro.core.types import EDGE_FIELDS, NODE_FIELDS, EdgeCtx, Workload

# ---------------------------------------------------------------- intervals


@dataclasses.dataclass(frozen=True)
class IVal:
    """Abstract value: closed interval [lo, hi] with provenance.

    lo/hi are jnp scalars or arrays (runtime values — the synthesized bound
    function is traced through this interpreter).  ``exact`` is static:
    lo is hi *by construction*.  ``taint`` is the set of runtime-varying
    input fields the endpoints depend on (drives PER_KERNEL vs PER_STEP).
    """

    lo: Any
    hi: Any
    exact: bool
    taint: FrozenSet[str] = frozenset()

    @staticmethod
    def point(x, taint: FrozenSet[str] = frozenset()) -> "IVal":
        return IVal(x, x, True, taint)


class Unsupported(Exception):
    pass


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BoundInputs:
    """Per-walker runtime scalars available to the synthesized estimators.

    h_min/h_max/h_mean are the per-node preprocessed stats (the generated
    preprocess() of Fig. 9d); the rest are the walker's concrete state.
    """

    h_min: jax.Array
    h_max: jax.Array
    h_mean: jax.Array
    deg_cur: jax.Array
    deg_prev: jax.Array
    cur: jax.Array
    prev: jax.Array
    step: jax.Array
    # per-walker WalkProgram state (a pytree; None for stateless programs).
    # Like cur/prev/step it is CONCRETE at bound-evaluation time — the
    # runtime knows each walker's state — so its leaves enter the abstract
    # interpreter as exact points, tainted "wstate" (any dependence makes
    # the bound PER_STEP and disqualifies the static/precomp regime).
    wstate: Any = None


PER_KERNEL = "PER_KERNEL"
PER_STEP = "PER_STEP"
FALLBACK = "FALLBACK"


@dataclasses.dataclass
class CompiledWorkload:
    """The output of Flexi-Compiler for one workload."""

    workload: Workload
    flag: str
    warnings: List[str]
    # bound_fn(bi: BoundInputs) -> (lo, hi) of w̃ over the node's edges
    bound_fn: Optional[Callable[[BoundInputs], Tuple[jax.Array, jax.Array]]]
    # sum_fn(bi: BoundInputs) -> estimate of Σ_u w̃(v, u)      (Eq. 12)
    sum_fn: Optional[Callable[[BoundInputs], jax.Array]]

    @property
    def usable(self) -> bool:
        return self.flag != FALLBACK


# ------------------------------------------------------------ interpreter


def _ctx_field_order() -> List[str]:
    probe = EdgeCtx(**{f: f for f in EDGE_FIELDS + NODE_FIELDS})
    leaves, _ = jax.tree_util.tree_flatten(probe)
    return list(leaves)


def _input_ivals(bi: BoundInputs, workload: Workload) -> Dict[str, IVal]:
    """Abstract values for each EdgeCtx field (§4.2 dependency classes)."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    if workload.weighted:
        h = IVal(f32(bi.h_min), f32(bi.h_max), False, frozenset({"h"}))
    else:
        h = IVal.point(f32(1.0))
    L = max(workload.num_labels, 1)
    return {
        "h": h,
        "label": IVal(i32(0), i32(L - 1), False),
        "dist": IVal(i32(0), i32(2), False),
        "nbr": IVal(i32(0), i32(np.iinfo(np.int32).max - 1), False),
        "deg_cur": IVal.point(i32(bi.deg_cur), frozenset({"deg_cur"})),
        "deg_prev": IVal.point(i32(bi.deg_prev), frozenset({"deg_prev"})),
        "cur": IVal.point(i32(bi.cur), frozenset({"cur"})),
        "prev": IVal.point(i32(bi.prev), frozenset({"prev"})),
        "step": IVal.point(i32(bi.step), frozenset({"step"})),
    }


def _hull(vals: List[IVal], extra_taint: FrozenSet[str] = frozenset()) -> IVal:
    lo = vals[0].lo
    hi = vals[0].hi
    for v in vals[1:]:
        lo = jnp.minimum(lo, v.lo)
        hi = jnp.maximum(hi, v.hi)
    taint = frozenset().union(*[v.taint for v in vals]) | extra_taint
    return IVal(lo, hi, False, taint)


def _mul(a: IVal, b: IVal) -> IVal:
    t = a.taint | b.taint
    if a.exact and b.exact:
        return IVal.point(a.lo * b.lo, t)
    c = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
    lo = jnp.minimum(jnp.minimum(c[0], c[1]), jnp.minimum(c[2], c[3]))
    hi = jnp.maximum(jnp.maximum(c[0], c[1]), jnp.maximum(c[2], c[3]))
    return IVal(lo, hi, False, t)


def _div(a: IVal, b: IVal) -> IVal:
    t = a.taint | b.taint
    if a.exact and b.exact:
        return IVal.point(a.lo / b.lo, t)
    if not b.exact:
        # Dividing by an uncertain quantity that may straddle zero cannot be
        # bounded statically — the paper's compiler has the same limitation
        # and falls back (§7.1).
        raise Unsupported("interval division by non-exact divisor")
    d = b.lo
    lo = jnp.minimum(a.lo / d, a.hi / d)
    hi = jnp.maximum(a.lo / d, a.hi / d)
    return IVal(lo, hi, False, t)


def _monotone(fn, a: IVal) -> IVal:
    if a.exact:
        return IVal.point(fn(a.lo), a.taint)
    return IVal(fn(a.lo), fn(a.hi), False, a.taint)


def _cmp(kind: str, a: IVal, b: IVal) -> IVal:
    t = a.taint | b.taint
    ops = {
        "lt": (lambda x, y: x < y),
        "le": (lambda x, y: x <= y),
        "gt": (lambda x, y: x > y),
        "ge": (lambda x, y: x >= y),
        "eq": (lambda x, y: x == y),
        "ne": (lambda x, y: x != y),
    }
    if a.exact and b.exact:
        return IVal.point(ops[kind](a.lo, b.lo), t)
    false = jnp.asarray(False)
    true = jnp.asarray(True)
    if kind in ("lt", "le"):
        strict = kind == "lt"
        certainly = (a.hi < b.lo) if strict else (a.hi <= b.lo)
        possibly = (a.lo < b.hi) if strict else (a.lo <= b.hi)
        return IVal(certainly, possibly, False, t)
    if kind in ("gt", "ge"):
        flipped = "lt" if kind == "gt" else "le"
        return _cmp(flipped, b, a)
    if kind == "eq":
        certainly = (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo)
        possibly = (a.lo <= b.hi) & (b.lo <= a.hi)
        return IVal(certainly, possibly, False, t)
    if kind == "ne":
        e = _cmp("eq", a, b)
        return IVal(~e.hi, ~e.lo, False, t)
    raise Unsupported(kind)


def _select_n(pred: IVal, *cases: IVal) -> IVal:
    if pred.exact:
        lo = jax.lax.select_n(pred.lo, *[c.lo for c in cases])
        hi = jax.lax.select_n(pred.lo, *[c.hi for c in cases])
        taint = pred.taint.union(*[c.taint for c in cases])
        return IVal(lo, hi, all(c.exact for c in cases), taint)
    if len(cases) == 2:
        # refine with the predicate's own bool interval:
        # pred.lo == certainly-true, pred.hi == possibly-true
        c0, c1 = cases
        hull = _hull([c0, c1], pred.taint)
        lo = jnp.where(pred.lo, c1.lo, jnp.where(~pred.hi, c0.lo, hull.lo))
        hi = jnp.where(pred.lo, c1.hi, jnp.where(~pred.hi, c0.hi, hull.hi))
        return IVal(lo, hi, False, hull.taint)
    return _hull(list(cases), pred.taint)


def _integer_pow(a: IVal, n: int) -> IVal:
    if a.exact:
        return IVal.point(a.lo**n, a.taint)
    if n % 2 == 1:
        return IVal(a.lo**n, a.hi**n, False, a.taint)
    c_lo, c_hi = a.lo**n, a.hi**n
    straddles = (a.lo <= 0) & (a.hi >= 0)
    lo = jnp.where(straddles, jnp.zeros_like(c_lo), jnp.minimum(c_lo, c_hi))
    return IVal(lo, jnp.maximum(c_lo, c_hi), False, a.taint)


_MONOTONE_UNARY = {
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "tanh": jnp.tanh,
    "logistic": jax.nn.sigmoid,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round_nearest_even": jnp.round,
    "sign": jnp.sign,
    "erf": jax.scipy.special.erf,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
}

_PASSTHROUGH = {"stop_gradient", "copy"}
# shape-changing but value-preserving: apply the primitive to both endpoints
_SHAPE_OPS = {"squeeze", "expand_dims", "transpose", "rev"}


def _eval_eqn(eqn, read: Callable[[Any], IVal]) -> List[IVal]:
    prim = eqn.primitive.name
    ins = [read(v) for v in eqn.invars]
    p = eqn.params

    if prim == "add":
        a, b = ins
        ex = a.exact and b.exact
        return [IVal(a.lo + b.lo, a.hi + b.hi, ex, a.taint | b.taint)]
    if prim == "sub":
        a, b = ins
        ex = a.exact and b.exact
        return [IVal(a.lo - b.hi, a.hi - b.lo, ex, a.taint | b.taint)]
    if prim == "mul":
        return [_mul(*ins)]
    if prim == "div":
        return [_div(*ins)]
    if prim == "neg":
        (a,) = ins
        return [IVal(-a.hi, -a.lo, a.exact, a.taint)]
    if prim == "abs":
        (a,) = ins
        if a.exact:
            return [IVal.point(jnp.abs(a.lo), a.taint)]
        straddles = (a.lo <= 0) & (a.hi >= 0)
        lo = jnp.where(straddles, jnp.zeros_like(a.lo), jnp.minimum(jnp.abs(a.lo), jnp.abs(a.hi)))
        hi = jnp.maximum(jnp.abs(a.lo), jnp.abs(a.hi))
        return [IVal(lo, hi, False, a.taint)]
    if prim == "max":
        a, b = ins
        return [IVal(jnp.maximum(a.lo, b.lo), jnp.maximum(a.hi, b.hi),
                     a.exact and b.exact, a.taint | b.taint)]
    if prim == "min":
        a, b = ins
        return [IVal(jnp.minimum(a.lo, b.lo), jnp.minimum(a.hi, b.hi),
                     a.exact and b.exact, a.taint | b.taint)]
    if prim in _MONOTONE_UNARY:
        return [_monotone(_MONOTONE_UNARY[prim], ins[0])]
    if prim == "integer_pow":
        return [_integer_pow(ins[0], p["y"])]
    if prim == "pow":
        a, b = ins
        if a.exact and b.exact:
            return [IVal.point(a.lo**b.lo, a.taint | b.taint)]
        if b.exact:  # monotone in base for base ≥ 0 (walk weights are)
            return [IVal(ins[0].lo ** b.lo, ins[0].hi ** b.lo, False,
                         a.taint | b.taint)]
        raise Unsupported("pow with non-exact exponent")
    if prim in ("lt", "le", "gt", "ge", "eq", "ne"):
        return [_cmp(prim, *ins)]
    if prim == "and":
        a, b = ins
        return [IVal(a.lo & b.lo, a.hi & b.hi, a.exact and b.exact, a.taint | b.taint)]
    if prim == "or":
        a, b = ins
        return [IVal(a.lo | b.lo, a.hi | b.hi, a.exact and b.exact, a.taint | b.taint)]
    if prim == "not":
        (a,) = ins
        return [IVal(~a.hi, ~a.lo, a.exact, a.taint)]
    if prim == "xor":
        a, b = ins
        if a.exact and b.exact:
            return [IVal.point(a.lo ^ b.lo, a.taint | b.taint)]
        return [IVal(jnp.asarray(False), jnp.asarray(True), False, a.taint | b.taint)]
    if prim == "select_n":
        return [_select_n(ins[0], *ins[1:])]
    if prim == "convert_element_type":
        (a,) = ins
        to = p["new_dtype"]
        return [IVal(jnp.asarray(a.lo, to), jnp.asarray(a.hi, to), a.exact, a.taint)]
    if prim in _PASSTHROUGH:
        (a,) = ins
        return [a]
    if prim in _SHAPE_OPS:
        (a,) = ins
        bind = lambda x: eqn.primitive.bind(x, **p)
        return [IVal(bind(a.lo), bind(a.hi), a.exact, a.taint)]
    if prim == "reshape" or prim == "broadcast_in_dim":
        (a,) = ins
        shape = p.get("new_sizes", p.get("shape"))
        dims = p.get("dimensions", p.get("broadcast_dimensions"))
        if prim == "reshape":
            f = lambda x: jax.lax.reshape(x, shape, dims)
        else:
            f = lambda x: jax.lax.broadcast_in_dim(x, shape, dims)
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim == "rem":
        a, b = ins
        if a.exact and b.exact:
            return [IVal.point(jax.lax.rem(a.lo, b.lo), a.taint | b.taint)]
        if b.exact:
            # lhs nonneg assumed (walk steps / labels); result ∈ [0, |b|-1]
            one = jnp.ones_like(b.lo)
            return [IVal(jnp.zeros_like(b.lo), jnp.abs(b.lo) - one, False,
                         a.taint | b.taint)]
        raise Unsupported("rem by non-exact divisor")
    if prim == "clamp":
        lo_b, x, hi_b = ins
        if not (lo_b.exact and hi_b.exact):
            raise Unsupported("clamp with non-exact bounds")
        f = lambda v: jnp.clip(v, lo_b.lo, hi_b.lo)
        return [IVal(f(x.lo), f(x.hi), x.exact, x.taint | lo_b.taint | hi_b.taint)]
    if prim in ("gather", "dynamic_slice"):
        op = ins[0]
        idxs = ins[1:]
        if all(i.exact for i in idxs):
            bind = lambda o: eqn.primitive.bind(o, *[i.lo for i in idxs], **p)
            taint = op.taint.union(*[i.taint for i in idxs]) if idxs else op.taint
            return [IVal(bind(op.lo), bind(op.hi), op.exact, taint)]
        # uncertain index ⇒ hull over the whole operand
        taint = op.taint.union(*[i.taint for i in idxs])
        shape = eqn.outvars[0].aval.shape
        lo = jnp.broadcast_to(jnp.min(op.lo), shape)
        hi = jnp.broadcast_to(jnp.max(op.hi), shape)
        return [IVal(lo, hi, False, taint)]
    if prim == "reduce_min":
        (a,) = ins
        f = lambda x: jnp.min(x, axis=tuple(p["axes"]))
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim == "reduce_max":
        (a,) = ins
        f = lambda x: jnp.max(x, axis=tuple(p["axes"]))
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim == "reduce_sum":
        (a,) = ins
        f = lambda x: jnp.sum(x, axis=tuple(p["axes"]))
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim == "reduce_or":
        (a,) = ins
        f = lambda x: jnp.any(x, axis=tuple(p["axes"]))
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim == "reduce_and":
        (a,) = ins
        f = lambda x: jnp.all(x, axis=tuple(p["axes"]))
        return [IVal(f(a.lo), f(a.hi), a.exact, a.taint)]
    if prim in ("jit", "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
                "custom_jvp_call_jaxpr", "remat", "checkpoint"):
        sub = p.get("jaxpr", p.get("call_jaxpr"))
        if sub is None:
            raise Unsupported(prim)
        closed = sub if isinstance(sub, jcore.ClosedJaxpr) else jcore.ClosedJaxpr(sub, [])
        return _interpret(closed, ins)
    raise Unsupported(prim)


def _interpret(closed: jcore.ClosedJaxpr, in_ivals: List[IVal]) -> List[IVal]:
    jaxpr = closed.jaxpr
    if len(in_ivals) != len(jaxpr.invars):
        # zip would silently truncate; fail loudly instead (typically a
        # wstate pytree whose structure differs from the trace template)
        raise Unsupported(
            f"input arity mismatch: {len(in_ivals)} abstract inputs for "
            f"{len(jaxpr.invars)} jaxpr inputs (wstate missing or "
            f"mis-structured?)")
    env: Dict[Any, IVal] = {}

    def read(v) -> IVal:
        if isinstance(v, jcore.Literal):
            return IVal.point(jnp.asarray(v.val))
        return env[v]

    for var, val in zip(jaxpr.constvars, closed.consts):
        env[var] = IVal.point(jnp.asarray(val))
    for var, val in zip(jaxpr.invars, in_ivals):
        env[var] = val
    for eqn in jaxpr.eqns:
        outs = _eval_eqn(eqn, read)
        for var, val in zip(eqn.outvars, outs):
            env[var] = val
    return [read(v) for v in jaxpr.outvars]


# ------------------------------------------------------------- public API


def _wstate_ivals(wstate) -> List[IVal]:
    """Abstract values for the program's per-walker state leaves.

    ``wstate`` is concrete at bound-evaluation time (the runtime holds
    every walker's state, like ``cur``/``prev``/``step``), so each leaf
    enters as an exact point — tainted ``"wstate"`` so dependence shows up
    in the flag lattice and the static-regime proof.  Array leaves indexed
    by per-edge fields (e.g. a visited set gathered at ``ctx.nbr``) flow
    through the existing uncertain-index gather rule: the hull over the
    leaf's actual values, which stays both sound and tight.
    """
    return [IVal.point(jnp.asarray(leaf), frozenset({"wstate"}))
            for leaf in jax.tree_util.tree_leaves(wstate)]


def analyze(workload: Workload, max_enum_labels: int = 8) -> CompiledWorkload:
    """Run Flexi-Compiler on a walk program.  Never raises: analysis
    failure returns flag=FALLBACK (the paper's eRVS-only safe mode) with
    warnings.  Accepts both :class:`~repro.core.types.WalkProgram` and the
    deprecated :class:`~repro.core.types.Workload` (whose ``edge_weight``
    drops the empty ``wstate`` — identical jaxpr, identical analysis).
    """
    params = workload.params()
    warnings: List[str] = []
    order = _ctx_field_order()

    template = EdgeCtx(
        h=jnp.float32(1.0), label=jnp.int32(0), dist=jnp.int32(1),
        nbr=jnp.int32(0), deg_cur=jnp.int32(1), deg_prev=jnp.int32(1),
        cur=jnp.int32(0), prev=jnp.int32(0), step=jnp.int32(0),
    )
    try:
        template_ws = workload.wstate_template()
        closed = jax.make_jaxpr(
            lambda c, ws: workload.edge_weight(c, params, ws)
        )(template, template_ws)
    except Exception as e:  # untraceable user code
        return CompiledWorkload(workload, FALLBACK,
                                [f"get_weight not traceable: {e!r}"], None, None)

    # --- probe the abstract interpreter once to decide flag/fallback -----
    probe_bi = BoundInputs(*(jnp.float32(1.0),) * 3, *(jnp.int32(1),) * 5,
                           wstate=template_ws)

    def bound_fn(bi: BoundInputs) -> Tuple[jax.Array, jax.Array]:
        field_ivals = _input_ivals(bi, workload)
        ins = [field_ivals[name] for name in order] + _wstate_ivals(bi.wstate)
        (out,) = _interpret(closed, ins)
        return (jnp.maximum(out.lo, 0.0).astype(jnp.float32),
                jnp.maximum(out.hi, 0.0).astype(jnp.float32))

    try:
        field_ivals = _input_ivals(probe_bi, workload)
        (probe_out,) = _interpret(
            closed, [field_ivals[n] for n in order]
            + _wstate_ivals(template_ws))
    except Unsupported as e:
        return CompiledWorkload(
            workload, FALLBACK,
            [f"unsupported primitive in get_weight: {e} — eRVS-only mode"],
            None, None)

    flag = PER_STEP if probe_out.taint else PER_KERNEL

    # --- sum estimator (Eq. 12): enumerate small domains, average --------
    L = min(max(workload.num_labels, 1), max_enum_labels)
    dists = (0, 1, 2) if workload.needs_dist else (1,)
    labels = tuple(range(L)) if workload.needs_labels else (0,)

    def sum_fn(bi: BoundInputs) -> jax.Array:
        h_val = bi.h_mean if workload.weighted else jnp.float32(1.0)
        acc = jnp.float32(0.0)
        cnt = 0
        for d, l in itertools.product(dists, labels):
            ctx = EdgeCtx(
                h=jnp.asarray(h_val, jnp.float32), label=jnp.int32(l),
                dist=jnp.int32(d), nbr=jnp.int32(0),
                deg_cur=jnp.asarray(bi.deg_cur, jnp.int32),
                deg_prev=jnp.asarray(bi.deg_prev, jnp.int32),
                cur=jnp.asarray(bi.cur, jnp.int32),
                prev=jnp.asarray(bi.prev, jnp.int32),
                step=jnp.asarray(bi.step, jnp.int32),
            )
            # the walker's actual state feeds the estimate (an Eq. 12-style
            # average, not a bound — exactness is not required here)
            acc = acc + jnp.maximum(
                workload.edge_weight(ctx, params, bi.wstate), 0.0)
            cnt += 1
        mean_w = acc / cnt
        return mean_w * jnp.maximum(bi.deg_cur, 0).astype(jnp.float32)

    return CompiledWorkload(workload, flag, warnings, bound_fn, sum_fn)


# ------------------------------------------------- static-regime analysis

# Inputs that vary with *walk state* (they change every step / every
# walker): the state-class EdgeCtx fields plus the program's own per-walker
# ``wstate``.  A get_weight whose output provably ignores all of them
# depends only on (edge data, current node) — so the transition
# distribution of a node is a constant of the graph and per-node ITS/alias
# tables can be built ONCE (the precomp regime of core/precomp.py; C-SAW's
# static case).
STATE_FIELDS = frozenset({"dist", "prev", "deg_prev", "step", "wstate"})


def static_taint(workload: Workload) -> Optional[FrozenSet[str]]:
    """Dependence set of ``get_weight``'s output over ALL EdgeCtx fields.

    Runs the provenance half of the abstract interpreter with every field
    entered as an *exact probe point tainted by its own name* (unlike the
    bound analysis, which only taints runtime-varying inputs).  Exact points
    keep every primitive inside the abstract domain, so this succeeds for
    any traceable get_weight; the value endpoints are meaningless, only the
    propagated taint is read.  Returns None when the workload cannot be
    traced or hits an unsupported primitive (conservative: treat as
    state-dependent).
    """
    params = workload.params()
    template = EdgeCtx(
        h=jnp.float32(1.0), label=jnp.int32(0), dist=jnp.int32(1),
        nbr=jnp.int32(0), deg_cur=jnp.int32(1), deg_prev=jnp.int32(1),
        cur=jnp.int32(0), prev=jnp.int32(0), step=jnp.int32(0),
    )
    try:
        template_ws = workload.wstate_template()
        closed = jax.make_jaxpr(
            lambda c, ws: workload.edge_weight(c, params, ws)
        )(template, template_ws)
    except Exception:
        return None
    probe = {
        "h": jnp.float32(1.0), "label": jnp.int32(0), "dist": jnp.int32(1),
        "nbr": jnp.int32(0), "deg_cur": jnp.int32(1),
        "deg_prev": jnp.int32(1), "cur": jnp.int32(0),
        "prev": jnp.int32(0), "step": jnp.int32(0),
    }
    ins = [IVal.point(probe[name], frozenset({name}))
           for name in _ctx_field_order()] + _wstate_ivals(template_ws)
    try:
        (out,) = _interpret(closed, ins)
    except Unsupported:
        return None
    return out.taint


def is_static(workload: Workload) -> bool:
    """True iff ``get_weight`` provably ignores the walk state.

    This is the gate of the precomp regime: a static workload's per-node
    transition distribution never changes, so ``core/precomp.py`` may bake
    it into ITS/alias tables at engine construction and samplers reduce to
    an O(log d) binary search / O(1) alias pick per step.
    """
    taint = static_taint(workload)
    return taint is not None and not (taint & STATE_FIELDS)


# ------------------------------------------------------- fusable analysis

# EdgeCtx fields the mega-step kernel cannot materialise per candidate
# edge: ``dist`` needs a binary search of prev's row per neighbour and
# ``label`` an extra gather stream — both stay on the staged path.  The
# kernel's tile builder substitutes the same neutral placeholders the
# transition-ctx contract documents (dist=1 in weight tiles, label=0), so
# a weight whose output provably ignores both fields evaluates to the
# SAME value in-kernel as staged — that proof is this gate.
FUSE_EDGE_EXCLUDED = frozenset({"dist", "label"})

# For the rejection regime the kernel wants the compiled upper bound as a
# per-NODE array baked before launch (one ``bound_fn`` eval per node, at
# placeholder deg_prev/prev/step/wstate).  Sound iff the bound provably
# ignores everything that is not node-local.
FUSE_BOUND_STATE = frozenset(
    {"dist", "label", "deg_prev", "prev", "step", "wstate"})


@dataclasses.dataclass(frozen=True)
class FuseReport:
    """Whether a walk program can be staged into the mega-step kernel.

    ``weight_fusable``   — ``get_weight`` is taint-analyzable and provably
                           ignores ``dist``/``label`` (the fields the
                           kernel cannot build per edge), so the in-kernel
                           tile/edge contexts reproduce the staged weight
                           values bit for bit.
    ``hooks_fusable``    — ``on_step``/``should_stop`` trace on the scalar
                           transition ctx and preserve the wstate
                           structure (the PR-4 "wstate fast path": state
                           updates run inside the kernel's step loop).
    ``bound_node_local`` — the compiled rejection bound depends only on
                           node-local inputs, so eRJS can consume a
                           per-node baked bound array instead of
                           re-deriving it per walker per step.

    A sampler needs at least ``weight_fusable and hooks_fusable``
    (``fusable``); the rejection regime additionally needs
    ``bound_node_local``.  Anything short of that falls back to the
    staged scan — mirroring the precomp gating, a miss is never unsound.
    """
    weight_fusable: bool
    hooks_fusable: bool
    bound_node_local: bool
    reasons: Tuple[str, ...] = ()

    @property
    def fusable(self) -> bool:
        return self.weight_fusable and self.hooks_fusable


def fuse_report(workload: Workload) -> FuseReport:
    """Decide per program what the mega-step kernel may stage in-kernel.

    Like :func:`analyze`, never raises: an untraceable or unsupported
    program simply reports non-fusable with the reason strings, and the
    engine keeps the staged scan.
    """
    reasons: List[str] = []
    taint = static_taint(workload)
    if taint is None:
        weight_fusable = False
        bound_node_local = False
        reasons.append("get_weight not analyzable (trace failed or "
                       "unsupported primitive) — staged fallback")
    else:
        bad = sorted(taint & FUSE_EDGE_EXCLUDED)
        flagged = [f for f, need in
                   [("dist", workload.needs_dist),
                    ("label", workload.needs_labels)] if need]
        weight_fusable = not bad and not flagged
        if bad:
            reasons.append(f"get_weight depends on {', '.join(bad)} — the "
                           f"kernel cannot build these per candidate edge")
        elif flagged:
            reasons.append(f"program requests {', '.join(flagged)} payloads "
                           f"the kernel does not materialise")
        bound_node_local = not (taint & FUSE_BOUND_STATE)
        if not bound_node_local:
            reasons.append(
                f"bound depends on non-node-local inputs "
                f"{sorted(taint & FUSE_BOUND_STATE)} — no baked per-node "
                f"bound; rejection stays staged")

    from repro.kernels.megastep_kernel import wstate_refusal
    why = wstate_refusal(jax.tree_util.tree_leaves(
        jax.eval_shape(workload.wstate_template)))
    hooks_fusable = why is None
    if why is not None:
        reasons.append(why)
    if workload.has_hooks:
        params = workload.params()
        template_ws = workload.wstate_template()
        tctx = EdgeCtx(
            h=jnp.float32(1.0), label=jnp.int32(-1), dist=jnp.int32(-1),
            nbr=jnp.int32(0), deg_cur=jnp.int32(1), deg_prev=jnp.int32(0),
            cur=jnp.int32(0), prev=jnp.int32(-1), step=jnp.int32(0),
        )
        if workload.on_step is not None:
            try:
                out = jax.eval_shape(
                    lambda: workload.on_step(tctx, params, template_ws))
                want = jax.eval_shape(lambda: template_ws)
                if (jax.tree_util.tree_structure(out)
                        != jax.tree_util.tree_structure(want)):
                    raise TypeError("on_step changes the wstate structure")
                for o, w in zip(jax.tree_util.tree_leaves(out),
                                jax.tree_util.tree_leaves(want)):
                    if o.shape != w.shape or o.dtype != w.dtype:
                        raise TypeError(
                            f"on_step leaf {o.shape}/{o.dtype} != "
                            f"{w.shape}/{w.dtype}")
            except Exception as e:
                hooks_fusable = False
                reasons.append(f"on_step not stageable: {e!r}")
        if workload.should_stop is not None:
            try:
                out = jax.eval_shape(
                    lambda: workload.should_stop(tctx, params, template_ws))
                if jnp.shape(out) != ():
                    raise TypeError(f"should_stop returns shape "
                                    f"{jnp.shape(out)}, want a scalar")
            except Exception as e:
                hooks_fusable = False
                reasons.append(f"should_stop not stageable: {e!r}")

    return FuseReport(weight_fusable=weight_fusable,
                      hooks_fusable=hooks_fusable,
                      bound_node_local=bound_node_local,
                      reasons=tuple(reasons))
