"""Counter-based RNG usable *inside* Pallas TPU kernels.

Threefry-2x32 (Salmon et al., SC'11) in plain 32-bit jnp ops — add/xor/rotl
only — so the same code path runs (a) inside a Pallas kernel body on TPU,
(b) in interpret mode on CPU, and (c) in the pure-jnp ref oracles.  Being
counter-based is what makes the paper's jump technique *actually free*: a
skipped (walker, block) simply never evaluates its counter (no stream to
advance).  On real TPU deployments this can be swapped for the native
``pltpu.prng_random_bits`` (hardware PRNG); the kernels take the generator
as a parameter.  Statistical quality: full 20-round Threefry, the same
generator family JAX's host PRNG uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """20-round Threefry-2x32: (key0, key1, ctr0, ctr1) -> (r0, r1), uint32."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):  # 5 blocks of 4 rounds = 20 rounds
        for r in range(4):
            rot = _ROTATIONS[(block % 2) * 4 + r]
            x0 = x0 + x1
            x1 = _rotl(x1, rot) ^ x0
        inj = block + 1
        x0 = x0 + ks[inj % 3]
        x1 = x1 + ks[(inj + 1) % 3] + jnp.uint32(inj)
    return x0, x1


def _top24(r):
    # via int32: exact (values < 2^24), and Mosaic has no uint32->f32 cast
    return (r >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)


def uniform_01(k0, k1, c0, c1):
    """U(0,1) floats (never exactly 0) from two 32-bit counters.

    Uses the top 24 bits → uniform on [2^-25, 1 - 2^-25] after the half-ulp
    shift; safe for log().
    """
    r0, _ = threefry2x32(k0, k1, c0, c1)
    return _top24(r0) * jnp.float32(1.0 / (1 << 24)) \
        + jnp.float32(0.5 / (1 << 24))


def uniform_pair_01(k0, k1, c0, c1):
    """Two independent U(0,1) streams from one threefry call."""
    r0, r1 = threefry2x32(k0, k1, c0, c1)
    scale = jnp.float32(1.0 / (1 << 24))
    half = jnp.float32(0.5 / (1 << 24))
    return _top24(r0) * scale + half, _top24(r1) * scale + half


# ------------------------------------------------------------------ streams
# Every sampler draws from its walker's per-step key (``threefry_seeds`` of
# ``WalkerState.stream_keys()``) at a counter pair of its own, so the
# staged selectors and the Pallas kernels call the SAME functions below and
# are bit-identical by construction:
#   eRVS tile t, offset j     → (t, j)           (t < 2^31)
#   eRJS trial counter c      → (ERJS_SALT, c)
#   ITS / alias table draws   → (0, ITS_SALT) / (0, ALIAS_SALT)  (j < tile)
ERJS_SALT = 0x8E75_0000
ITS_SALT = 0x175CDF
ALIAS_SALT = 0xA11A5


def threefry_seeds(rng: jax.Array) -> jax.Array:
    """[W] typed per-(walker, step) keys (or their raw [W, 2] data) →
    [W, 2] uint32 Threefry key words."""
    data = (jax.random.key_data(rng)
            if jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key)
            else rng)
    return jnp.asarray(data, jnp.uint32).reshape(data.shape[0], -1)[:, :2]


def tile_uniforms(k0, k1, t, j):
    """eRVS key uniforms: offset ``j`` of neighbour tile ``t``."""
    return uniform_01(k0, k1, t, j)


def trial_uniform(k0, k1, c):
    """eRJS proposal/acceptance uniform number ``c`` of a step."""
    return uniform_01(k0, k1, jnp.uint32(ERJS_SALT), c)
