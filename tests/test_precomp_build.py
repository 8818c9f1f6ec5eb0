"""The bucketed table build (``core/precomp.py`` ``_table_rows``): rows
bucketed by degree in powers of two, each bucket's alias tables built by
Vose's construction in lock-step over its rows.

It must equal the per-row definition (``_row_tables``) bit for bit, on
every kind of row: empty, one entry, zero weights, a row in every
bucket, rows above 4,096 entries; a row rebuilt after a weight update
must equal a fresh build; and each row's (prob, alias) must give back
w / sum(w) to float32 rounding."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, WalkEngine, build_tables, rebuild_rows
from repro.core import precomp
from repro.core.precomp import edge_weights_static
from repro.graphs.csr import CSRGraph
from repro.walks import deepwalk

FIELDS = ("cdf", "total", "alias_off", "alias_prob", "invalid")


def row_degrees(seed: int) -> np.ndarray:
    """Degrees with empty and one-entry rows, 20 rows in each power-of-two
    bucket up to 2**11 (so every bucket runs the lock-step), one row in
    each bucket above, two rows above 4,096, in a shuffled order."""
    rng = np.random.default_rng(seed)
    degs = [0, 0, 1, 1, 1]
    for k in range(12):
        degs += rng.integers(1 << k, 1 << (k + 1), 20).tolist()
    degs += [5000, 8191, 9000]
    degs = np.asarray(degs, np.int64)
    rng.shuffle(degs)
    return degs


def weights(degs: np.ndarray, seed: int) -> np.ndarray:
    """U[0, 5) weights, about 5% of entries zero, three rows all zero,
    one row with a single heavy entry, and rows of small whole weights,
    whose Vose updates hit exactly 1.0 (the tie between the stacks)."""
    rng = np.random.default_rng(seed + 100)
    starts = np.concatenate([[0], np.cumsum(degs)[:-1]])
    w = rng.uniform(0.0, 5.0, int(degs.sum()))
    w[rng.random(w.size) < 0.05] = 0.0
    rows = np.nonzero(degs > 1)[0]
    for v in rows[:3]:
        w[starts[v]:starts[v] + degs[v]] = 0.0
    v = rows[3]
    w[starts[v]] = 1e4
    for v in rows[4::3]:
        w[starts[v]:starts[v] + degs[v]] = rng.integers(1, 5, degs[v])
    return w.astype(np.float32)


def graph_of(degs: np.ndarray, w: np.ndarray, seed: int) -> CSRGraph:
    rng = np.random.default_rng(seed + 200)
    V = degs.size
    indptr = np.concatenate([[0], np.cumsum(degs)])
    indices = np.concatenate([np.sort(rng.choice(V, min(d, V),
                                                 replace=False))
                              if d <= V else rng.integers(0, V, d)
                              for d in degs]).astype(np.int32)
    return CSRGraph(indptr=jnp.asarray(indptr, jnp.int32),
                    indices=jnp.asarray(indices),
                    h=jnp.asarray(w),
                    labels=jnp.zeros(indices.shape, jnp.int32))


def per_row_tables(w64: np.ndarray, indptr: np.ndarray):
    """The per-row definition, one ``_row_tables`` call per row."""
    V = indptr.size - 1
    cdf = np.zeros(w64.size, np.float32)
    total = np.zeros(V, np.float32)
    alias = np.zeros(w64.size, np.int32)
    prob = np.ones(w64.size, np.float32)
    for v in range(V):
        s, e = int(indptr[v]), int(indptr[v + 1])
        if e > s:
            cdf[s:e], total[v], alias[s:e], prob[s:e] = \
                precomp._row_tables(w64[s:e])
    return cdf, total, alias, prob


def assert_bits_equal(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                  err_msg=what)


@pytest.mark.parametrize("min_rows", [1, precomp._LOCKSTEP_MIN_ROWS])
@pytest.mark.parametrize("seed", [0, 1])
def test_bucketed_build_equals_the_per_row_build(seed, min_rows,
                                                 monkeypatch):
    """``min_rows`` 1 runs every bucket in lock-step, the default builds
    the few longest rows one by one."""
    monkeypatch.setattr(precomp, "_LOCKSTEP_MIN_ROWS", min_rows)
    degs = row_degrees(seed)
    g = graph_of(degs, weights(degs, seed), seed)
    wl = deepwalk(weighted=True)
    tables = build_tables(g, wl, wl.params(), aligned=False)
    w64 = np.asarray(edge_weights_static(g, wl, wl.params()), np.float64)
    want = per_row_tables(w64, np.asarray(g.indptr, np.int64))
    got = (tables.cdf, tables.total, tables.alias_off, tables.alias_prob)
    for name, a, b in zip(("cdf", "total", "alias", "prob"), got, want):
        assert_bits_equal(a, b, name)


def test_alias_tables_give_back_the_row_distribution():
    """Column j of a row of degree d is drawn with probability
    (prob[j] + sum of (1 - prob[i]) over columns i aliased to j) / d,
    which must be w[j] / sum(w) up to the float32 rounding of each prob
    entry it sums."""
    degs = row_degrees(2)
    w = weights(degs, 2).astype(np.float64)
    indptr = np.concatenate([[0], np.cumsum(degs)])
    _, total, alias, prob = precomp._table_rows(w, indptr[:-1], degs)
    checked = 0
    for v in range(degs.size):
        s, e = int(indptr[v]), int(indptr[v + 1])
        d = e - s
        if d == 0 or w[s:e].sum() == 0:
            assert d == 0 or total[v] == 0
            continue
        p, a = prob[s:e].astype(np.float64), alias[s:e]
        assert ((a >= 0) & (a < d)).all()
        drawn = (p + np.bincount(a, weights=1.0 - p, minlength=d)) / d
        want = w[s:e] / w[s:e].sum()
        aliased_to = np.bincount(a, minlength=d)
        tol = (1 + aliased_to) * 2.0**-24 / d + 1e-12
        assert (np.abs(drawn - want) <= tol).all(), v
        checked += 1
    assert checked > 200


def test_rebuilt_rows_after_update_graph_equal_a_fresh_build():
    degs = row_degrees(3)
    g = graph_of(degs, weights(degs, 3), 3)
    wl = deepwalk(weighted=True)
    eng = WalkEngine(g, wl, EngineConfig(method="alias_precomp"))
    rng = np.random.default_rng(4)
    indptr = np.asarray(g.indptr)
    touched = np.nonzero(degs > 0)[0][[0, 5, 17, -1]]
    h = np.asarray(g.h).copy()
    for v in touched:
        s, e = indptr[v], indptr[v + 1]
        h[s:e] *= rng.uniform(0.2, 3.0, e - s).astype(np.float32)
    h[indptr[touched[1]]:indptr[touched[1] + 1]] = 0.0  # a row gone dark
    g2 = CSRGraph(indptr=g.indptr, indices=g.indices, h=jnp.asarray(h),
                  labels=g.labels)
    eng.update_graph(g2, invalidated=touched)
    assert np.asarray(eng.precomp.invalid)[touched].all()
    assert eng.drain_rebuilds() == touched.size
    fresh = build_tables(g2, wl, wl.params(), aligned=False)
    for f in FIELDS:
        assert_bits_equal(getattr(eng.precomp, f), getattr(fresh, f), f)
    # rebuild_rows alone, on tables of the old weights
    old = build_tables(g, wl, wl.params(), aligned=False)
    again = rebuild_rows(old, g2, wl, wl.params(), touched, scatter="copy")
    for f in FIELDS:
        assert_bits_equal(getattr(again, f), getattr(fresh, f), f)


def test_vose_build_is_the_bucketed_build():
    """``_vose_build`` (the kernels' test tables) serves the same tables."""
    degs = row_degrees(5)
    w = weights(degs, 5).astype(np.float64)
    indptr = np.concatenate([[0], np.cumsum(degs)])
    alias, prob = precomp._vose_build(w, indptr)
    _, _, want_a, want_p = per_row_tables(w, indptr)
    assert_bits_equal(alias, want_a, "alias")
    assert_bits_equal(prob, want_p, "prob")
