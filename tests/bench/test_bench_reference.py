"""The numpy reference against brute-force enumeration on tiny graphs,
and the comparison's control: the reference computed in bfloat16 in the
sampler's place must come out not correct."""
import itertools

import numpy as np
import pytest

from benchpath import bench_module

reference = bench_module("reference")
graphgen = bench_module("graphgen")

N2V = {"kind": "node2vec", "a": 2.0, "b": 0.5}
PPR = {"kind": "ppr_nibble", "alpha": 0.15, "eps": 0.02}


def tiny_graph(seed=0, n=9):
    rng = np.random.default_rng(seed)
    edges = {(s, d) for s, d in itertools.product(range(n), range(n))
             if s != d and rng.random() < 0.45}
    edges |= {(i, (i + 1) % n) for i in range(n)}
    src, dst = np.array(sorted(edges)).T
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    h = rng.uniform(1, 5, src.size).astype(np.float32)
    return reference.Graph(indptr, dst, h), edges


def brute_force(g, edges, spec, prev, v):
    """Transition probabilities straight from the program's definition."""
    out = {}
    for x in range(g.V):
        if (v, x) not in edges:
            continue
        w = float(g.h[np.nonzero((np.repeat(np.arange(g.V), g.deg) == v)
                                 & (g.indices == x))[0][0]])
        if spec["kind"] == "node2vec" and prev >= 0:
            w *= (1 / spec["a"] if x == prev else
                  1.0 if (prev, x) in edges else 1 / spec["b"])
        out[x] = w
    tot = sum(out.values())
    return {x: w / tot for x, w in out.items()}


@pytest.mark.parametrize("spec", [N2V, {"kind": "deepwalk"}, PPR])
def test_row_weights_match_brute_force(spec):
    g, edges = tiny_graph()
    for v in range(g.V):
        for prev in [-1] + [p for p in range(g.V) if (p, v) in edges]:
            nbr, w = reference.row_weights(spec, g, prev, v)
            want = brute_force(g, edges, spec, prev, v)
            assert nbr.tolist() == sorted(want)
            assert np.allclose(w / w.sum(), [want[x] for x in nbr],
                               rtol=1e-12)


def exact_draws(spec, g, prev, cur, rng):
    out = np.empty(len(cur), np.int64)
    for i in range(len(cur)):
        nbr, w = reference.row_weights(spec, g, int(prev[i]), int(cur[i]))
        out[i] = rng.choice(nbr, p=w / w.sum())
    return out


def positions(g, n, rng):
    e = rng.integers(0, g.indices.size, n)
    return np.repeat(np.arange(g.V), g.deg)[e], g.indices[e]


def test_pit_is_uniform_for_exact_draws_and_not_for_a_biased_sampler():
    g, _ = tiny_graph(1)
    rng = np.random.default_rng(5)
    prev, cur = positions(g, 20_000, rng)
    good = exact_draws(N2V, g, prev, cur, rng)
    assert reference.ks_sqrt_n(
        reference.pit_values(N2V, g, prev, cur, good, rng)) < 2.0
    # ignoring node2vec's bias (a deepwalk step) is a different law
    biased = exact_draws({"kind": "deepwalk"}, g, prev, cur, rng)
    assert reference.ks_sqrt_n(
        reference.pit_values(N2V, g, prev, cur, biased, rng)) > 2.7
    # a hop off the graph is never uniform
    off = good.copy()
    off[0] = cur[0]
    assert reference.ks_sqrt_n(
        reference.pit_values(N2V, g, prev, cur, off, rng)) == np.inf


def test_ppr_stop_rule_by_hand():
    # mass after k hops is 0.85^k; stop once it is below 0.02 * deg
    degs = np.array([10, 10, 30, 2])
    # 0.85 < 0.2? no; 0.7225 < 0.2? no; 0.614 < 0.6? no; 0.522 < 0.04 no
    assert reference.ppr_stops(PPR, degs).tolist() == [False] * 4
    degs = np.array([10, 40, 2])
    # 0.85 < 0.2 no; 0.7225 < 0.8 yes
    assert reference.ppr_stops(PPR, degs).tolist() == [False, True, False]


def test_length_errors_for_ppr_walks():
    # a path whose hop out of a degree-100 node must end the walk
    indptr = np.array([0, 1, 101, 102] + [102] * 98)
    V = indptr.size - 1
    indices = np.concatenate([[1], np.arange(2, 102) % V, [1]])
    g = reference.Graph(indptr, indices, np.ones(indices.size, np.float32))
    spec = dict(PPR, walk_len=5)
    done = np.array([True])
    # 0 -> 1: out of node 0 (degree 1) mass 0.85 >= 0.02, go on;
    # 1 -> 2: out of node 1 (degree 100) mass 0.7225 < 2, stop
    right = np.array([[0, 1, 2, -1, -1, -1]])
    assert reference.length_errors(spec, g, right, done) == 0
    too_long = np.array([[0, 1, 2, 1, -1, -1]])
    assert reference.length_errors(spec, g, too_long, done) == 1
    too_short = np.array([[0, 1, -1, -1, -1, -1]])
    assert reference.length_errors(spec, g, too_short, done) == 1


def test_form_errors():
    g, edges = tiny_graph(2)
    src, dst = next(iter(sorted(edges)))
    nxt = next(x for x in range(g.V) if (dst, x) in edges)
    good = np.array([[src, dst, nxt, -1]])
    assert reference.form_errors(g, np.array([src]), good) == 0
    assert reference.form_errors(g, np.array([dst]), good) == 1
    resumed = np.array([[src, dst, -1, nxt]])
    assert reference.form_errors(g, np.array([src]), resumed) >= 1
    stay = np.array([[src, src, -1, -1]])
    assert reference.form_errors(g, np.array([src]), stay) == 1


def test_control_fails_at_a_power_law_scale():
    """The control (the reference in bfloat16 in the sampler's place)
    against the exact reference, on a SCALE 14 graph of the
    configurations' generator: its hub rows are long enough that a
    bfloat16 running sum stops growing."""
    cfg = {"generator": "graph500_kronecker", "scale": 14, "edgefactor": 16,
           "a": 0.57, "b": 0.19, "c": 0.19, "seed": 1, "weights": "uniform"}
    ip, ix, h = graphgen.make_graph(cfg, 9, cache=False)
    g = reference.Graph(ip, ix, h)
    rng = np.random.default_rng(3)
    prev, cur = positions(g, 10000, rng)
    for spec in (N2V, {"kind": "deepwalk"}):
        ctl = reference.control_draws(spec, g, prev, cur, rng)
        ctl_ks = reference.ks_sqrt_n(
            reference.pit_values(spec, g, prev, cur, ctl, rng))
        good = exact_draws(spec, g, prev, cur, rng)
        ks = reference.ks_sqrt_n(
            reference.pit_values(spec, g, prev, cur, good, rng))
        assert ks < 2.7 < ctl_ks, (spec, ks, ctl_ks)
