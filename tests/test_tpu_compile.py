"""Mosaic compiles of the walk kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, scalar memory that grows
with the walker pool, dynamic slices of vectors, unsupported casts.
These tests lower and compile each kernel of the main path at the widths
``chip_smoke.py`` runs — no chip needed, about two seconds each.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, so a test worker
that is not given this file must not touch it.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import WalkerState
from repro.walks import deepwalk, ppr_nibble

#: walkers of the smoke's offline phases / slots of its served phase
W_OFFLINE, W_SERVED = 16384, 1024
STEPS, SERVE_EPOCH = 80, 8
TILE = 256  # EngineConfig's default eRVS tile
#: aligned rows of a 2^20-node, average-degree-16 power-law graph
#: (every row on a 128-lane boundary, plus slack), rounded up
ROWS = (1 << 20) + (1 << 18)
NODE_ROWS = (1 << 20) // 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described device cannot be read back from a
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kernel", ["its_search", "alias_pick"])
def test_precomp_kernel_compiles(one_chip, kernel):
    from repro.kernels import precomp_kernel
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    lanes = (s((W_OFFLINE,), jnp.int32), s((W_OFFLINE,), jnp.int32),
             s((W_OFFLINE,), jnp.float32), s((W_OFFLINE, 2), jnp.uint32))
    stream = s((ROWS, 128), jnp.float32)
    if kernel == "its_search":
        _compile(lambda c, *a: precomp_kernel.its_search(
            c, *a, interpret=False), stream, *lanes)
    else:
        _compile(lambda p, a, *r: precomp_kernel.alias_pick(
            p, a, *r, interpret=False), stream, stream, *lanes)


# (kind, program, slots, epoch_len): the four fused regimes at the
# smoke's offline widths, ppr_nibble's wstate leaf at served widths, and
# the alias regime at the dw-alias-offline-pl20 cell's 4,096 slots and
# 16-step epochs
MEGASTEP_CELLS = [
    ("reservoir", "deepwalk", W_OFFLINE, STEPS),
    ("rejection", "deepwalk", W_OFFLINE, STEPS),
    ("precomp_its", "deepwalk", W_OFFLINE, STEPS),
    ("precomp_alias", "ppr_nibble", W_SERVED, SERVE_EPOCH),
    ("precomp_alias", "deepwalk", 4096, 16),
]


@pytest.mark.parametrize("kind,program,W,T", MEGASTEP_CELLS)
def test_megastep_compiles(one_chip, kind, program, W, T):
    from repro.kernels.megastep_kernel import make_streamed_epoch
    wl = {"deepwalk": deepwalk, "ppr_nibble": ppr_nibble}[program]()
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    i32 = lambda: s((W,), jnp.int32)
    ws = jax.tree_util.tree_map(lambda l: s((W,) + l.shape, l.dtype),
                                wl.wstate_template())
    state = WalkerState(cur=i32(), prev=i32(), step=i32(),
                        alive=s((W,), jnp.bool_),
                        rng=s((W, 2), jnp.uint32), wstate=ws)
    node_i, node_f = s((NODE_ROWS, 128), jnp.int32), s((NODE_ROWS, 128),
                                                      jnp.float32)
    edge_i, edge_f = s((ROWS, 128), jnp.int32), s((ROWS, 128), jnp.float32)
    streams = [node_i, node_i, edge_i, edge_f]
    if kind == "rejection":
        streams.append(node_f)
    V = 1 << 20
    tables = dict(total=s((V,), jnp.float32), invalid=s((V,), jnp.bool_),
                  cdf2d=edge_f, prob2d=edge_f, alias2d=edge_f)
    epoch = make_streamed_epoch(wl, wl.params(), kind=kind, tile=TILE,
                                interpret=False)

    def run(state, tables, streams):
        return epoch(state, types.SimpleNamespace(**tables), streams, T,
                     STEPS, 512)

    compiled = _compile(run, state, tables, streams)
    # the kernel is named by its regime, so a trace can attribute it
    assert f"megastep_{kind}" in compiled.as_text()
