"""Precomputed-regime Pallas TPU kernels — CDF binary search + alias pick.

TPU-native form of the ``core/precomp.py`` selectors (DESIGN.md §3.1 layout:
tables live in the tile-aligned [R, 128] stream of ``ops.align_rows``, every
node's row starting on a 128-lane boundary):

* :func:`its_search` — each walker performs an O(log d) binary search of
  its row's baked inclusive-prefix CDF.  Each probe DMAs only the (8, 128)
  tile holding the probed element HBM→VMEM — ~log₂(d) small copies
  instead of streaming the whole row, which is the entire point of the
  precomputed regime (C-SAW).  Probes of a converged search are never
  issued (while_loop, not a fixed-depth fori).
* :func:`alias_pick` — O(1): two uniforms, one DMA into the prob stream and
  one into the alias stream, then accept-or-alias.

Both run a block of walkers per grid step (:func:`walker_grid`): the
per-walker scalars ride (B,) SMEM windows, so scalar memory does not grow
with the pool, and a scalar is picked out of a VMEM tile with a dynamic
sublane load plus a one-lane masked max (:func:`read_elem`) — the forms
Mosaic (the Pallas TPU compiler) accepts.

RNG is the same counter-based Threefry-2x32 the other kernels use
(kernels/prng.py), with per-kernel salts so table draws never collide with
the eRVS/eRJS streams.  Both kernels are validated bit-exactly against the
``ref.its_search_ref`` / ``ref.alias_pick_ref`` oracles in interpret mode
(tests/test_kernels.py).

These kernels are the default execution path of the engine's
``its_precomp``/``alias_precomp`` samplers on TPU
(``EngineConfig.precomp_exec``; see ``samplers.precomp_table_select``) —
the jnp selectors in ``core/precomp.py`` consume the same Threefry
(key, counter, salt) triples, so the two paths are bit-identical and the
knob only ever changes throughput.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import (ALIAS_SALT, ITS_SALT, uniform_01,
                                uniform_pair_01)
from repro.kernels.ref import LANES, SUBLANES, TILE


#: walkers per grid step for pools larger than one block.  A multiple of
#: 1024 because XLA tiles rank-1 int32 arrays T(1024) on TPU, and a (B,)
#: SMEM window must match that tiling (or span the whole array).
BLOCK = 1024


def default_interpret() -> bool:
    """Whether ``pallas_call`` should run in interpret mode on the current
    backend: compiled on TPU, interpreted (the semantic reference, bit-
    identical) everywhere else."""
    return jax.default_backend() != "tpu"


def walker_grid(W: int):
    """(block B, padded pool Wp): one block holding the whole pool when it
    fits, else ``BLOCK``-walker blocks over a pool padded to a multiple."""
    B = W if W <= BLOCK else BLOCK
    return B, -(-W // B) * B


def pad_lanes(x, Wp: int):
    """Zero-pad a per-walker array's leading dim to ``Wp`` lanes (pad
    lanes read as empty: degree 0, not alive)."""
    extra = Wp - x.shape[0]
    if not extra:
        return x
    return jnp.pad(x, [(0, extra)] + [(0, 0)] * (x.ndim - 1))


def smem_lanes(B: int):
    """BlockSpec of one walker block's window of a rank-1 [Wp] array."""
    return pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM)


def lane_pick(vec, idx, where=None):
    """The element of ``vec`` at flat index ``idx`` as a scalar: a masked
    max (exact for any value, -0.0 and NaN included) in place of the
    dynamic slice Mosaic cannot lower.  ``where`` overrides the flat
    index grid (e.g. a tile's offsets)."""
    if where is None:
        where = (jax.lax.broadcasted_iota(jnp.int32, vec.shape, 0)
                 * vec.shape[-1]
                 + jax.lax.broadcasted_iota(jnp.int32, vec.shape, 1))
    low = (jnp.float32(-jnp.inf) if jnp.issubdtype(vec.dtype, jnp.floating)
           else jnp.iinfo(vec.dtype).min)
    return jnp.max(jnp.where(where == idx, vec, low))


def dma_tiles(row, *streams):
    """Copy the (8, 128) tile at sublane-row ``row`` of each ``(hbm, buf,
    sem)`` stream into its VMEM buffer, all copies in flight together."""
    copies = [pltpu.make_async_copy(hbm.at[pl.ds(row, SUBLANES), :], buf,
                                    sem) for hbm, buf, sem in streams]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()


def read_elems(r0, pos, *streams):
    """Element ``pos`` (≥ 0) of the aligned row starting at sublane-row
    ``r0`` of each ``(hbm, buf, sem)`` [R, 128] stream: one tile DMA per
    stream, one sublane load, one lane pick."""
    blk = pos // TILE
    dma_tiles(r0 + blk * SUBLANES, *streams)
    within = pos - blk * TILE
    return tuple(lane_pick(buf[pl.ds(within // LANES, 1), :],
                           within % LANES) for _, buf, _ in streams)


def read_elem(hbm, buf, sem, r0, pos):
    """:func:`read_elems` of one stream."""
    return read_elems(r0, pos, (hbm, buf, sem))[0]


def _its_kernel(row0_ref, degs_ref, totals_ref, k0_ref, k1_ref,  # SMEM (B,)
                cdf_hbm,  # ANY (HBM) [R, 128] tile-aligned CDF stream
                off_ref,  # output SMEM (B,)
                buf, sem):  # scratch: VMEM (8, 128), DMA sem
    def walker(b, carry):
        r0 = row0_ref[b]
        deg = degs_ref[b]
        total = totals_ref[b]
        u = uniform_01(k0_ref[b], k1_ref[b], jnp.uint32(0),
                       jnp.uint32(ITS_SALT))
        target = u * total

        # first offset in [0, deg) whose inclusive prefix exceeds the
        # target; align_rows pads the stream with ≥ 2 slack tiles, so a
        # probe's tile DMA never runs off the end even for the last row
        def cond(c):
            lo, hi = c
            return lo < hi

        def body(c):
            lo, hi = c
            mid = (lo + hi) // 2
            go_right = read_elem(cdf_hbm, buf, sem, r0, mid) <= target
            return (jnp.where(go_right, mid + 1, lo),
                    jnp.where(go_right, hi, mid))

        lo, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), deg))
        sel = jnp.clip(lo, 0, jnp.maximum(deg - 1, 0))
        off_ref[b] = jnp.where((deg > 0) & (total > 0), sel, -1)
        return carry

    jax.lax.fori_loop(0, off_ref.shape[0], walker, 0)


def _per_walker(W, row0, degs, totals, seeds):
    B, Wp = walker_grid(W)
    seeds = jnp.asarray(seeds, jnp.uint32)
    lanes = [pad_lanes(jnp.asarray(x, dt), Wp) for x, dt in
             ((row0, jnp.int32), (degs, jnp.int32), (totals, jnp.float32),
              (seeds[:, 0], jnp.uint32), (seeds[:, 1], jnp.uint32))]
    return B, Wp, lanes


@partial(jax.jit, static_argnames=("interpret",))
def its_search(cdf2d: jax.Array, row0: jax.Array, degs: jax.Array,
               totals: jax.Array, seeds: jax.Array, interpret: bool = True):
    """Inverse-transform draw via DMA-probed binary search.

    cdf2d [R,128] f32 (aligned row-local inclusive prefixes), row0/degs [W]
    int32, totals [W] f32, seeds [W,2] uint32.
    Returns offset [W] int32 within each row (-1 for empty/zero rows).
    """
    W = row0.shape[0]
    B, Wp, lanes = _per_walker(W, row0, degs, totals, seeds)
    out = pl.pallas_call(
        _its_kernel,
        grid=(Wp // B,),
        in_specs=[smem_lanes(B)] * 5
        + [pl.BlockSpec(memory_space=pl.ANY)],  # CDF stays in HBM
        out_specs=smem_lanes(B),
        out_shape=jax.ShapeDtypeStruct((Wp,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(*lanes, cdf2d)
    return out[:W]


def _alias_kernel(row0_ref, degs_ref, totals_ref, k0_ref, k1_ref,  # SMEM
                  prob_hbm, alias_hbm,  # ANY (HBM) [R, 128] streams
                  off_ref,  # output SMEM (B,)
                  buf_p, buf_a, sem_p, sem_a):  # scratch
    def walker(b, carry):
        r0 = row0_ref[b]
        deg = degs_ref[b]
        total = totals_ref[b]
        u1, u2 = uniform_pair_01(k0_ref[b], k1_ref[b], jnp.uint32(0),
                                 jnp.uint32(ALIAS_SALT))
        col = jnp.minimum((u1 * deg.astype(jnp.float32)).astype(jnp.int32),
                          jnp.maximum(deg - 1, 0))
        p_col, a_col = read_elems(r0, col, (prob_hbm, buf_p, sem_p),
                                  (alias_hbm, buf_a, sem_a))
        sel = jnp.where(u2 < p_col, col, a_col.astype(jnp.int32))
        off_ref[b] = jnp.where((deg > 0) & (total > 0), sel, -1)
        return carry

    jax.lax.fori_loop(0, off_ref.shape[0], walker, 0)


@partial(jax.jit, static_argnames=("interpret",))
def alias_pick(prob2d: jax.Array, alias2d: jax.Array, row0: jax.Array,
               degs: jax.Array, totals: jax.Array, seeds: jax.Array,
               interpret: bool = True):
    """O(1) alias draw: column = ⌊u₁·d⌋, keep iff u₂ < prob else alias.

    prob2d/alias2d [R,128] f32 aligned Vose tables (alias offsets stored
    as float32 — exact for rows up to 2²⁴ neighbours, asserted by the
    table builder), row0/degs [W] int32, totals [W] f32, seeds [W,2].
    Returns offset [W] int32 within each row (-1 for empty/zero rows).
    """
    W = row0.shape[0]
    B, Wp, lanes = _per_walker(W, row0, degs, totals, seeds)
    out = pl.pallas_call(
        _alias_kernel,
        grid=(Wp // B,),
        in_specs=[smem_lanes(B)] * 5
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2,  # prob/alias in HBM
        out_specs=smem_lanes(B),
        out_shape=jax.ShapeDtypeStruct((Wp,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(*lanes, prob2d, alias2d)
    return out[:W]
