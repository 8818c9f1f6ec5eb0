"""node2vec's ``dist`` search (``graphs/csr.py`` ``has_edge``) at the depth
the engine derives from its padded row length, ``search_depth(pad)``.

The search must stay exact at that depth: membership equals a numpy
``searchsorted`` test for every row length up to ``pad``, on a CSR and on
a delta overlay whose inserts grow a row past the old ``pad``.  Walks
must be byte-identical to the int32-safe 32-round search, on one device
and on four; the staged epoch's search loops must run ``search_depth(pad)``
rounds; and ``StepStats.dist_searches`` must count the searches run."""
import contextlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, WalkEngine
from repro.core import baselines, ervs, runtime, samplers
from repro.core.ctxutil import single_edge_ctx, tile_ctx
from repro.core.types import StepStats
from repro.graphs import power_law_graph
from repro.graphs.csr import INT32_DEPTH, from_edges, has_edge, search_depth
from repro.walks import deepwalk, node2vec


def row_lengths(pad: int):
    """0–3 and 2^k - 1, 2^k, 2^k + 1 for every power of two up to ``pad``,
    none longer than ``pad`` (the bound the depth is derived from)."""
    lens = {0, 1, 2, 3, pad}
    k = 1
    while k <= pad:
        lens |= {k - 1, k, k + 1}
        k *= 2
    return sorted(n for n in lens if n <= pad)


def rows_graph(lens):
    """Node i's row holds 2, 4, ..., 2 * lens[i]; every row shares those
    values, so a probe that left its row would find a false match."""
    src = np.concatenate([np.full(n, i) for i, n in enumerate(lens)])
    dst = np.concatenate([2 * np.arange(1, n + 1) for n in lens])
    num_nodes = max(len(lens), 2 * max(lens) + 3)
    return from_edges(src, dst, num_nodes)


@pytest.mark.parametrize("pad", [1, 2, 8, 64, 256])
def test_has_edge_matches_searchsorted(pad):
    lens = row_lengths(pad)
    g = rows_graph(lens)
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    # every value from below the first entry to above the last: the
    # entries (first and last among them) and the gaps between them
    cands = np.arange(0, 2 * pad + 3)
    v = np.repeat(np.arange(len(lens)), cands.size)
    u = np.tile(cands, len(lens))
    depth = search_depth(pad)
    got = np.asarray(jax.vmap(lambda a, b: has_edge(g, a, b, depth))(
        jnp.asarray(v, jnp.int32), jnp.asarray(u, jnp.int32)))
    row = [indices[indptr[i]:indptr[i + 1]] for i in v]
    pos = np.array([np.searchsorted(r, x) for r, x in zip(row, u)])
    want = np.array([p < r.size and r[p] == x
                     for r, p, x in zip(row, pos, u)])
    np.testing.assert_array_equal(got, want)
    assert want.sum() == sum(lens)
    # no previous node: never an edge
    assert not bool(has_edge(g, jnp.int32(-1), jnp.int32(2), depth))


def test_search_depth_of_the_cell_graph():
    # Graph500 SCALE 20: largest row 64,482, pad 65,536
    assert search_depth(65_536) == 18
    assert search_depth(2 ** 31 - 1) == INT32_DEPTH == 32


def ref_dist(rows, prev, u):
    """node2vec's dist(prev, u) from host rows."""
    if prev < 0:
        return 1
    if u == prev:
        return 0
    return 1 if u in set(rows[prev].tolist()) else 2


def host_rows(graph):
    start = np.asarray(graph.row_starts(jnp.arange(graph.num_nodes)))
    deg = np.asarray(graph.row_degs(jnp.arange(graph.num_nodes)))
    idx = np.asarray(graph.indices)
    return [idx[s:s + d] for s, d in zip(start, deg)]


@contextlib.contextmanager
def full_depth():
    """Every staged caller searches INT32_DEPTH rounds, as before the depth
    came from ``pad``; traces cached at the other depth are dropped on the
    way in and out."""
    with pytest.MonkeyPatch.context() as mp:
        for mod in (ervs, samplers, baselines, runtime):
            mp.setattr(mod, "search_depth", lambda n: INT32_DEPTH)
        jax.clear_caches()
        yield
    jax.clear_caches()


QUERIES, STEPS, SLOTS, EPOCH = 24, 7, 8, 3


def corpus(graph, method, devices=None, **cfg):
    """(paths, StepStats totals) of QUERIES node2vec walks streamed through
    SLOTS slots in EPOCH-step epochs."""
    eng = WalkEngine(graph, node2vec(),
                     EngineConfig(method=method, tile=16, step_exec="staged",
                                  rjs_trials=2, rjs_max_rounds=2, **cfg))
    sched = eng.scheduler(num_steps=STEPS, key=jax.random.key(5),
                          slots=SLOTS, epoch_len=EPOCH, capacity=QUERIES,
                          devices=devices)
    pending = list(range(QUERIES))
    starts = (np.arange(QUERIES, dtype=np.int32) * 7) % graph.num_nodes
    while pending or sched.busy:
        n = sched.free_slots().size
        take, pending = pending[:n], pending[n:]
        if take:
            sched.admit(take, starts[take])
        sched.run_epoch()
    return eng, sched.paths, dict(sched.totals)


def pl_graph():
    return power_law_graph(300, 8, seed=4)


@pytest.mark.parametrize("method", ["adaptive", "erjs"])
def test_walks_match_the_32_round_search(method):
    g = pl_graph()
    eng, paths, totals = corpus(g, method)
    assert eng.dist_depth == search_depth(eng.pad) < INT32_DEPTH
    with full_depth():
        _, ref_paths, ref_totals = corpus(g, method)
    np.testing.assert_array_equal(paths, ref_paths)
    assert totals == ref_totals
    assert totals["dist_searches"] > 0


def search_loop_lengths(jaxpr):
    """Lengths of every ``scan`` (a ``fori_loop`` with static bounds) whose
    body gathers, in ``jaxpr`` and the jaxprs nested in it."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            subs = []
            for val in eqn.params.values():
                for v in val if isinstance(val, (tuple, list)) else (val,):
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        subs.append(inner)
            if eqn.primitive.name == "scan" and any(
                    e.primitive.name == "gather" for s in subs
                    for e in s.eqns):
                found.append(eqn.params["length"])
            for s in subs:
                walk(s)

    walk(jaxpr.jaxpr)
    return found


def epoch_jaxpr(eng):
    sched = eng.scheduler(num_steps=STEPS, key=jax.random.key(5),
                          slots=SLOTS, epoch_len=EPOCH, capacity=QUERIES)
    sched.admit(list(range(SLOTS)), np.arange(SLOTS, dtype=np.int32))
    return eng._epoch_fn.trace(
        sched.state, sched.tables, sched.graph_view, sched.stats_view,
        epoch_len=EPOCH, num_steps=STEPS, pad=sched.pad_view,
        max_tiles=sched.max_tiles_view, shards=1).jaxpr


def test_staged_epoch_searches_search_depth_rounds():
    g = pl_graph()
    eng = WalkEngine(g, node2vec(),
                     EngineConfig(method="adaptive", tile=16,
                                  step_exec="staged", rjs_trials=2))
    depth = search_depth(eng.pad)
    assert depth not in (EPOCH, 2, INT32_DEPTH)
    lengths = search_loop_lengths(epoch_jaxpr(eng))
    # the eRJS trials, the plain and the hub eRVS passes each search
    assert lengths.count(depth) >= 3, lengths
    assert INT32_DEPTH not in lengths, lengths
    with full_depth():
        lengths = search_loop_lengths(epoch_jaxpr(eng))
    assert lengths.count(INT32_DEPTH) >= 3, lengths
    assert depth not in lengths, lengths


def test_overlay_row_grown_past_pad():
    """Inserts grow node 0's row from 4 to 40 entries, past the old pad
    of 16: the pad, and with it the depth, follow, and the dist codes of
    the grown row stay exact."""
    n = 64
    src = np.concatenate([np.zeros(4, np.int64), np.arange(1, n)])
    dst = np.concatenate([[5, 9, 20, 33], (np.arange(1, n) % (n - 1)) + 1])
    g = from_edges(src, dst, n)
    wl = node2vec()
    eng = WalkEngine(g, wl, EngineConfig(method="adaptive", tile=16,
                                         step_exec="staged"))
    assert eng.pad == 16
    new = np.arange(40, 40 + 36) % n
    new = new[~np.isin(new, [0, 5, 9, 20, 33])]
    eng.apply_updates(inserts=(np.zeros(new.size, np.int64), new,
                               np.ones(new.size, np.float32)))
    assert eng.overlay_active
    assert int(eng.graph.row_degs(jnp.int32(0))) == 4 + new.size > 16
    assert eng.pad == 64 and eng.dist_depth == search_depth(64)
    rows = host_rows(eng.graph)
    # lanes whose previous node is the grown row, at every current node
    cur = jnp.arange(n, dtype=jnp.int32)
    prev = jnp.zeros(n, jnp.int32).at[0].set(-1)
    step = jnp.ones(n, jnp.int32)
    ctx, mask = tile_ctx(eng.graph, wl, cur, prev, step, jnp.int32(0),
                         eng.pad, depth=eng.dist_depth)
    want = [[ref_dist(rows, int(p), int(u)) for u in r]
            for p, r in zip(np.asarray(prev), np.asarray(ctx.nbr))]
    np.testing.assert_array_equal(np.where(mask, ctx.dist, -9),
                                  np.where(mask, want, -9))
    # one rejection trial per lane: candidates across the grown row itself
    cands = jnp.asarray(np.arange(n) % 40, jnp.int32)
    ctx1, valid = single_edge_ctx(eng.graph, wl, jnp.zeros(n, jnp.int32),
                                  cur, step, cands, depth=eng.dist_depth)
    want1 = [ref_dist(rows, int(p), int(u))
             for p, u in zip(np.asarray(cur), np.asarray(ctx1.nbr))]
    np.testing.assert_array_equal(np.where(valid, ctx1.dist, -9),
                                  np.where(valid, want1, -9))
    # a node2vec step over the overlay walks as the 32-round search does
    key = jax.random.key(2)
    starts = np.arange(16, dtype=np.int32) % 8
    paths, _ = eng.walk_batch(starts, key, 5)
    with full_depth():
        ref, _ = eng.walk_batch(starts, key, 5)
    np.testing.assert_array_equal(np.asarray(paths), np.asarray(ref))


def clique(n: int):
    src, dst = np.nonzero(~np.eye(n, dtype=bool))
    return from_edges(src, dst, n)


@pytest.mark.parametrize("method", ["ervs", "erjs", "interleaved"])
def test_dist_searches_count(method):
    """K_9, unweighted node2vec with a = b = 1: every weight is 1, so the
    bound 1 accepts every first trial.  A step of W walkers then runs
    ceil(8 / tile) tile trips (eRVS, interleaved: trips · W · tile
    searches) or one round of K trials (eRJS: K · W)."""
    W, steps, tile, trials = 4, 5, 4, 3
    eng = WalkEngine(clique(9), node2vec(a=1.0, b=1.0, weighted=False),
                     EngineConfig(method=method, tile=tile,
                                  step_exec="staged", rjs_trials=trials))
    _, stats = eng.walk_batch(np.arange(W, dtype=np.int32),
                              jax.random.key(0), steps)
    totals = stats.host_totals()
    assert totals["live"] == W * steps
    per_step = trials * W if method == "erjs" else 2 * W * tile
    np.testing.assert_array_equal(np.asarray(stats.dist_searches),
                                  np.full(steps, per_step))
    assert totals["dist_searches"] == steps * per_step
    if method == "erjs":
        assert totals["rjs_served"] == W * steps
    if method == "ervs":
        assert totals["dist_searches"] == totals["ervs_lane_trips"] * tile


def test_dist_searches_zero_without_dist():
    eng = WalkEngine(clique(9), deepwalk(),
                     EngineConfig(method="adaptive", tile=4,
                                  step_exec="staged"))
    _, stats = eng.walk_batch(np.arange(4, dtype=np.int32),
                              jax.random.key(0), 3)
    assert stats.host_totals()["dist_searches"] == 0
    assert StepStats.from_flag_bits(
        jnp.zeros((4, 3), jnp.int32)).host_totals()["dist_searches"] == 0


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {tests!r})
import jax
import numpy as np
import test_dist_search as t

assert len(jax.devices()) == 4, jax.devices()
g = t.pl_graph()
for method in ("adaptive", "erjs"):
    _, paths, totals = t.corpus(g, method, devices=4)
    with t.full_depth():
        _, ref_paths, ref_totals = t.corpus(g, method, devices=4)
    np.testing.assert_array_equal(paths, ref_paths, err_msg=method)
    assert totals == ref_totals, (method, totals, ref_totals)
    assert totals["dist_searches"] > 0, method
print("DEPTH-X4-OK")
"""


def test_walks_match_the_32_round_search_on_four_devices():
    src = _CHILD.format(tests=str(Path(__file__).resolve().parent))
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(root / "src"),
             "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert "DEPTH-X4-OK" in out.stdout, out.stdout + out.stderr
