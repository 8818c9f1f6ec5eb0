"""Statistical correctness of every sampling method + engine behaviour:
sampler-registry resolution, chi-square equivalence of each registered
sampler against the exact transition distribution, and the streaming
epoch scheduler (refill, pad-lane masking, batch invariance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostModel, EngineConfig, METHODS, Sampler,
                        SamplerCaps, Selection, WalkEngine, WalkerState,
                        analyze, available_samplers, get_sampler,
                        register_sampler, BoundInputs, exact_probs)
from repro.core.baselines import (als_step, its_step, rjs_maxreduce_step,
                                  rvs_prefix_step)
from repro.core.erjs import erjs_step
from repro.core import ervs as ervs_mod
from repro.core.ervs import ervs_jump_step, ervs_step
from repro.core.ctxutil import degrees_of
from repro.graphs import node_stats, power_law_graph, random_graph
from repro.walks import deepwalk, node2vec, second_order_pagerank

N = 3000
PAD = 64


@pytest.fixture(scope="module")
def setup():
    g = random_graph(60, 6, seed=3)
    wl = node2vec()
    params = wl.params()
    v, pv, st = 7, 3, 2
    p, nbr = exact_probs(g, wl, params, v, pv, st, pad=PAD)
    cur = jnp.full((N,), v, jnp.int32)
    prev = jnp.full((N,), pv, jnp.int32)
    step = jnp.full((N,), st, jnp.int32)
    rng = jax.random.split(jax.random.key(0), N)
    return g, wl, params, p, nbr, cur, prev, step, rng


def tvd(samples, p, nbr):
    f = np.zeros_like(p)
    for i, n_ in enumerate(nbr):
        if n_ >= 0:
            f[i] = np.sum(samples == n_)
    f = f / max(len(samples), 1)
    return 0.5 * np.abs(f - p)[nbr >= 0].sum()

# TVD guard: for ~15 categories at N=3000, E[TVD] ≈ 0.02; 0.06 is ~3σ.
TVD_MAX = 0.06


class TestDistributions:
    def test_ervs(self, setup):
        g, wl, params, p, nbr, cur, prev, step, rng = setup
        out = np.asarray(ervs_step(g, wl, params, cur, prev, step, rng,
                                   tile=32, max_tiles=4))
        assert tvd(out, p, nbr) < TVD_MAX

    def test_ervs_jump(self, setup):
        g, wl, params, p, nbr, cur, prev, step, rng = setup
        out = ervs_jump_step(g, wl, params, cur, prev, step, rng,
                             tile=32, max_tiles=4)
        assert tvd(np.asarray(out), p, nbr) < TVD_MAX

    def test_erjs_with_compiler_bound(self, setup):
        g, wl, params, p, nbr, cur, prev, step, rng = setup
        stats = node_stats(g)
        comp = analyze(wl)
        bi = BoundInputs(h_min=stats.h_min[cur], h_max=stats.h_max[cur],
                         h_mean=stats.h_mean[cur],
                         deg_cur=degrees_of(g, cur),
                         deg_prev=degrees_of(g, prev),
                         cur=cur, prev=prev, step=step)
        _, bmax = jax.vmap(comp.bound_fn)(bi)
        nxt, fb, _ = erjs_step(g, wl, params, cur, prev, step, rng, bmax,
                               max_rounds=32)
        out = np.asarray(nxt)[~np.asarray(fb)]
        assert len(out) > 0.9 * N  # bound tight enough to mostly accept
        assert tvd(out, p, nbr) < TVD_MAX

    @pytest.mark.parametrize("fn", [its_step, als_step, rvs_prefix_step,
                                    rjs_maxreduce_step])
    def test_baselines(self, setup, fn):
        g, wl, params, p, nbr, cur, prev, step, rng = setup
        out = np.asarray(fn(g, wl, params, cur, prev, step, rng, pad=PAD))
        assert tvd(out, p, nbr) < TVD_MAX


class TestEngine:
    @pytest.mark.parametrize("method", ["adaptive", "ervs", "erjs", "its",
                                        "als", "rvs_prefix",
                                        "rjs_maxreduce", "random", "degree",
                                        "its_precomp", "alias_precomp",
                                        "interleaved"])
    def test_walks_stay_on_graph(self, method):
        g = random_graph(200, 8, seed=1)
        eng = WalkEngine(g, node2vec(), EngineConfig(method=method, tile=64))
        res = eng.run(np.arange(48), num_steps=6)
        paths = res.paths
        assert paths.shape == (48, 7)
        indptr = np.asarray(g.indptr)
        indices = np.asarray(g.indices)
        for q in range(0, 48, 7):
            for t in range(6):
                a, b = paths[q, t], paths[q, t + 1]
                if b < 0:
                    break
                assert b in indices[indptr[a]:indptr[a + 1]], \
                    f"{method}: {a}->{b} is not an edge"

    def test_all_methods_agree_statistically(self):
        """End-to-end: step-1 visit distribution similar across methods."""
        g = random_graph(100, 8, seed=5)
        dists = {}
        for method in ["ervs", "its", "adaptive"]:
            eng = WalkEngine(g, deepwalk(),
                             EngineConfig(method=method, tile=64))
            res = eng.run(np.zeros(2000, np.int32), num_steps=1,
                          key=jax.random.key(7))
            dists[method] = np.bincount(res.paths[:, 1], minlength=100) / 2000
        for m in ["its", "adaptive"]:
            d = 0.5 * np.abs(dists[m] - dists["ervs"]).sum()
            assert d < 0.08, f"{m} vs ervs TVD={d}"

    def test_2ndpr_and_metapath_run(self):
        from repro.walks import metapath
        g = random_graph(150, 6, seed=2)
        for wl in [second_order_pagerank(), metapath()]:
            eng = WalkEngine(g, wl, EngineConfig(method="adaptive", tile=64))
            res = eng.run(np.arange(32), num_steps=5)
            assert res.paths.shape == (32, 6)

    def test_cost_model_prefers_rvs_under_skew(self):
        cm = CostModel(edge_cost_ratio=4.0)
        deg = jnp.full((4,), 100, jnp.int32)
        # uniform-ish weights: sum ≈ deg·mean ≫ ratio·max ⇒ RJS
        assert bool(cm.prefer_rjs(jnp.float32(5.0)[None],
                                  jnp.float32(300.0)[None], deg[:1])[0])
        # heavy skew: ratio·max > sum ⇒ RVS
        assert not bool(cm.prefer_rjs(jnp.float32(100.0)[None],
                                      jnp.float32(300.0)[None], deg[:1])[0])


# ---------------------------------------------------------------- registry
def chi2_critical(df: int, z: float = 3.7) -> float:
    """Wilson–Hilferty upper-tail chi-square quantile (z=3.7 ≈ p 1e-4)."""
    a = 2.0 / (9.0 * df)
    return df * (1.0 - a + z * np.sqrt(a)) ** 3


class _UniformTestSampler(Sampler):
    """Degree-uniform proposal — a minimal user-defined strategy."""

    name = "test_uniform"
    caps = SamplerCaps(supports_partition=True)

    def select(self, ctx, state, rng, *, active):
        from repro.core.ctxutil import degrees_of
        deg = degrees_of(ctx.graph, state.cur)
        u = jax.vmap(lambda k: jax.random.uniform(k, ()))(rng)
        off = jnp.minimum((u * deg).astype(jnp.int32),
                          jnp.maximum(deg - 1, 0))
        pos = jnp.clip(ctx.graph.indptr[state.cur] + off, 0,
                       ctx.graph.num_edges - 1)
        nxt = jnp.where(deg > 0, ctx.graph.indices[pos], -1)
        zero = jnp.int32(0)
        return Selection(next_nodes=jnp.where(active, nxt, -1),
                         rjs_served=zero, fallbacks=zero)


class TestSamplerRegistry:
    def test_methods_snapshot_matches_registry(self):
        """METHODS is a sorted snapshot of the built-in registry; the
        registry (also sorted) may only grow around it."""
        assert METHODS == tuple(sorted(METHODS))
        assert set(METHODS) <= set(available_samplers())
        for name in METHODS:
            assert get_sampler(name).name == name

    def test_available_samplers_deterministic(self):
        assert available_samplers() == tuple(sorted(available_samplers()))
        assert available_samplers() == available_samplers()

    def test_new_strategies_registered(self):
        for name in ["its_precomp", "alias_precomp", "interleaved"]:
            assert name in available_samplers()

    def test_unknown_method_rejected(self):
        # EngineConfig itself validates, naming the known samplers
        with pytest.raises(ValueError, match="registered"):
            EngineConfig(method="nope")
        with pytest.raises(ValueError, match="adaptive"):
            EngineConfig(method="nope")
        with pytest.raises(KeyError):
            get_sampler("nope")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_sampler(get_sampler("ervs"))

    def test_custom_sampler_end_to_end(self):
        """A user-registered sampler runs via EngineConfig(method=name)."""
        from repro.core import samplers as samplers_mod
        register_sampler(_UniformTestSampler(), overwrite=True)
        try:
            g = random_graph(150, 8, seed=4)
            eng = WalkEngine(g, deepwalk(),
                             EngineConfig(method="test_uniform", tile=64))
            res = eng.run(np.arange(24), num_steps=5, batch=7)
        finally:
            del samplers_mod._REGISTRY["test_uniform"]
        assert res.paths.shape == (24, 6)
        indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
        for q in range(24):
            for t in range(5):
                a, b = res.paths[q, t], res.paths[q, t + 1]
                if b < 0:
                    break
                assert b in indices[indptr[a]:indptr[a + 1]]

    @pytest.mark.parametrize("name", METHODS)
    def test_chi_square_equivalence(self, name, setup):
        """Each registered sampler's one-step draw matches exact_probs."""
        g, wl, params, p, nbr, cur, prev, step, rng = setup
        eng = WalkEngine(g, wl, EngineConfig(method=name, tile=32))
        state = WalkerState(
            cur=cur, prev=prev, step=step,
            alive=jnp.ones((N,), bool),
            rng=jax.random.key_data(rng),
        )
        sel = eng.sampler.select(eng.sampler_ctx, state, rng,
                                 active=jnp.ones((N,), bool))
        out = np.asarray(sel.next_nodes)
        support = nbr[(nbr >= 0) & (p > 0)]
        probs = p[(nbr >= 0) & (p > 0)]
        assert np.isin(out, support).all(), \
            f"{name}: sampled outside the support: {set(out) - set(support)}"
        counts = np.array([(out == v).sum() for v in support])
        expected = probs * N
        chi2 = float(((counts - expected) ** 2 / expected).sum())
        crit = chi2_critical(len(support) - 1)
        assert chi2 < crit, f"{name}: chi2={chi2:.1f} ≥ crit={crit:.1f}"


# ------------------------------------------------- streaming epoch scheduler
class TestStreamingScheduler:
    def test_batch_invariance_non_multiple(self):
        """13 queries through 4 slots ≡ 13 queries at once, bit-for-bit —
        streams are keyed per query, refills happen at epoch boundaries,
        and pad/dead lanes never contribute to paths or telemetry."""
        g = random_graph(200, 8, seed=1)
        eng = WalkEngine(g, node2vec(), EngineConfig(method="adaptive",
                                                     tile=64))
        full = eng.run(np.arange(13), num_steps=9, key=jax.random.key(3))
        slotted = eng.run(np.arange(13), num_steps=9, key=jax.random.key(3),
                          batch=4, epoch_len=2)
        np.testing.assert_array_equal(full.paths, slotted.paths)
        assert full.live_steps == slotted.live_steps == 13 * 9
        assert full.frac_rjs == slotted.frac_rjs
        assert full.rjs_fallbacks == slotted.rjs_fallbacks

    def test_tail_epoch_telemetry_unskewed(self):
        """5 queries through 2 slots leaves a 1-walker tail epoch; the
        idle slot must not dilute frac_rjs (the old pad-the-tail chunking
        averaged node-0 pad walkers into it)."""
        g = random_graph(120, 8, seed=2)
        eng = WalkEngine(g, node2vec(), EngineConfig(method="erjs", tile=64))
        full = eng.run(np.arange(5), num_steps=6, key=jax.random.key(1))
        slotted = eng.run(np.arange(5), num_steps=6, key=jax.random.key(1),
                          batch=2)
        assert slotted.live_steps == full.live_steps == 5 * 6
        assert slotted.frac_rjs == full.frac_rjs > 0.5
        # all live steps are accounted for by emitted path entries
        assert (slotted.paths[:, 1:] >= 0).sum() == slotted.live_steps

    def test_early_death_slots_are_refilled(self):
        """metapath walks can dead-end early; their slots must be handed
        to queued queries and dead lanes must stop counting."""
        from repro.walks import metapath
        g = random_graph(150, 6, seed=2)
        eng = WalkEngine(g, metapath(), EngineConfig(method="adaptive",
                                                     tile=64))
        full = eng.run(np.arange(31), num_steps=5, key=jax.random.key(2))
        slotted = eng.run(np.arange(31), num_steps=5,
                          key=jax.random.key(2), batch=8, epoch_len=1)
        np.testing.assert_array_equal(full.paths, slotted.paths)
        assert full.live_steps == slotted.live_steps
        # dead lanes excluded: live steps == emitted entries + dead-end
        # attempts, both bounded by Q × L and < Q × L when walks die early
        assert slotted.live_steps <= 31 * 5
        assert (slotted.paths[:, 1:] >= 0).sum() <= slotted.live_steps

    def test_zero_queries(self):
        g = random_graph(50, 4, seed=0)
        eng = WalkEngine(g, deepwalk(), EngineConfig(method="ervs", tile=64))
        res = eng.run(np.zeros((0,), np.int32), num_steps=4)
        assert res.paths.shape == (0, 5)
        assert res.live_steps == 0 and res.frac_rjs == 0.0

    def test_walk_batch_matches_run(self):
        """walk_batch (the sharded entry point) agrees with run() when
        query order equals slot order."""
        g = random_graph(100, 8, seed=5)
        eng = WalkEngine(g, deepwalk(), EngineConfig(method="ervs", tile=64))
        starts = np.arange(16, dtype=np.int32)
        key = jax.random.key(9)
        paths_b, stats = eng.walk_batch(starts, key, 6)
        res = eng.run(starts, num_steps=6, key=key)
        np.testing.assert_array_equal(np.asarray(paths_b), res.paths[:, 1:])
        assert int(np.asarray(stats.live).sum()) == res.live_steps


# ------------------------------------------------ reservoir lane compaction
class TestLaneCompaction:
    """``PartitionedSampler`` runs each reservoir pass on its partition's
    lanes only, ``LANE_CHUNK`` at a time (``core/ervs.py``
    ``compact_lanes``).  A lane's pick reads only its own row, keys and
    state, so picks, whole paths and every other counter must be those of
    the dense pass, which runs when a chunk would hold the whole pool."""

    W, K, HUB = 32, 8, 24
    DENSE = 1 << 30  # LANE_CHUNK of at least W: the dense pass

    def engine(self, method):
        # one rejection trial in one round leaves many lanes to the §7.1
        # fallback, so "erjs" hands its reservoir side a real partition
        g = power_law_graph(300, 8, seed=4)
        return WalkEngine(g, node2vec(), EngineConfig(
            method=method, tile=16, jump_threshold=self.HUB, rjs_trials=1,
            rjs_max_rounds=1, step_exec="staged"))

    def lanes(self, eng):
        """A [W] walker state on nodes of every degree, hubs among them."""
        deg = np.asarray(eng.graph.degrees())
        rng = np.random.default_rng(0)
        nodes = np.argsort(-deg)[:200]
        cur = rng.choice(nodes, self.W, replace=False)
        cur[:4] = nodes[:4]  # the largest rows: the hub pass
        indptr, indices = (np.asarray(a) for a in (eng.graph.indptr,
                                                   eng.graph.indices))
        prev = np.asarray([indices[indptr[v]] for v in cur])
        prev[::5] = -1  # first steps: no previous node
        return WalkerState(
            cur=jnp.asarray(cur, jnp.int32),
            prev=jnp.asarray(prev, jnp.int32),
            step=jnp.asarray(rng.integers(0, 9, self.W), jnp.int32),
            alive=jnp.ones(self.W, bool),
            rng=jax.random.key_data(
                jax.random.split(jax.random.key(5), self.W)),
            carry=None,
            wstate=eng.workload.init_wstate_batch(
                jnp.arange(self.W, dtype=jnp.int32)))

    def passes(self, eng, state, masks):
        """_reservoir_select's (next, trips, edges, lane-trips) per mask."""
        ctx = eng.sampler_ctx
        deg = degrees_of(eng.graph, state.cur)
        f = jax.jit(lambda on: eng.sampler._reservoir_select(
            ctx, state, state.stream_keys(), deg, on))
        return [tuple(np.asarray(x) for x in f(jnp.asarray(m)))
                for m in masks]

    def drive(self, eng, n_queries=70):
        sched = eng.scheduler(num_steps=9, key=jax.random.key(3),
                              slots=self.W, epoch_len=4, capacity=n_queries)
        pending = list(range(n_queries))
        while pending or sched.busy:
            n = sched.free_slots().size
            take, pending = pending[:n], pending[n:]
            if take:
                sched.admit(take, np.asarray(take, np.int32) % 300)
            sched.run_epoch()
        return sched

    @pytest.mark.parametrize("method", ["adaptive", "erjs"])
    def test_compacted_passes_equal_dense(self, method, monkeypatch):
        order = np.random.default_rng(1).permutation(self.W)
        # active counts: none, under K, exactly K, several chunks, all W
        masks = []
        for n in (0, 5, self.K, 19, self.W):
            m = np.zeros(self.W, bool)
            m[order[:n]] = True
            masks.append(m)
        got = {}
        for chunk in (self.DENSE, self.K):
            monkeypatch.setattr(ervs_mod, "LANE_CHUNK", chunk)
            eng = self.engine(method)
            state = self.lanes(eng)
            got[chunk] = (self.passes(eng, state, masks), self.drive(eng))
        deg = np.asarray(degrees_of(eng.graph, state.cur))
        if method == "adaptive":  # the hub pass has lanes of its own
            assert (deg[masks[-1]] >= self.HUB).any()
            assert (deg[masks[-1]] < self.HUB).any()
        (dense, d_run), (comp, c_run) = got[self.DENSE], got[self.K]
        for m, d, c in zip(masks, dense, comp):
            np.testing.assert_array_equal(d[0], c[0])  # picks, -2 inactive
            assert (c[0][~m] == -2).all() and (c[0][m] >= 0).all()
            assert (d[1], d[2]) == (c[1], c[2])  # trips, edges
            assert c[3] <= d[3] == d[1] * self.W  # lane-trips
        assert comp[0][3] == 0 and comp[1][3] < dense[1][3]
        # whole runs: byte-identical paths, equal counters but lane-trips
        # and the dist searches they run (the eRJS trials' stay equal)
        np.testing.assert_array_equal(d_run.paths, c_run.paths)
        lanes = {k: v for k, v in d_run.totals.items()
                 if k not in ("ervs_lane_trips", "dist_searches")}
        assert lanes == {k: c_run.totals[k] for k in lanes}
        trials = [r.totals["dist_searches"] - r.totals["ervs_lane_trips"] * 16
                  for r in (d_run, c_run)]
        assert trials[0] == trials[1] > 0
        assert d_run.totals["ervs_lane_trips"] == \
            d_run.totals["ervs_trips"] * self.W
        assert 0 < c_run.totals["ervs_lane_trips"] \
            < d_run.totals["ervs_lane_trips"]
