"""The open-loop and corpus schedules: deterministic per seed, at the
rate they claim, with the skew they claim."""
import numpy as np
import pytest

from benchpath import bench_module

traffic = bench_module("traffic")

MIX = {"kind": "open_loop", "arrivals": "poisson", "rate_qps": 250.0,
       "zipf_theta": 0.99, "mix": {"deepwalk": 0.5, "ppr_nibble": 0.5}}


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(MIX, 10_000, 2**31 + 7, -5.0, 30.0)
    b = traffic.open_loop(MIX, 10_000, 2**31 + 7, -5.0, 30.0)
    c = traffic.open_loop(MIX, 10_000, 2**31 + 8, -5.0, 30.0)
    for k in ("due", "start", "program"):
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["start"], c["start"])
    # every seed offers the same amount of work, in another order
    assert a["due"].size == c["due"].size
    assert np.bincount(a["program"]).tolist() == \
        np.bincount(c["program"]).tolist()


@pytest.mark.parametrize("arrivals", ["poisson", "burst"])
def test_open_loop_rate(arrivals):
    mix = dict(MIX, arrivals=arrivals, burst_period_s=2.0)
    s = traffic.open_loop(mix, 10_000, 3, -4.0, 30.0)
    due = s["due"]
    assert np.all(np.diff(due) >= 0)
    assert due.min() >= -4.0 and due.max() < 30.0
    window = (due >= 0).sum()
    assert window == pytest.approx(250.0 * 30.0, rel=0.02)
    assert np.bincount(s["program"]).tolist() == [due.size // 2] * 2
    if arrivals == "burst":
        times, counts = np.unique(due, return_counts=True)
        assert np.allclose(np.diff(times), 2.0)
        assert set(counts.tolist()) == {500}
    else:
        gaps = np.diff(due)
        # exponential inter-arrivals: mean 1/rate, coefficient of variation 1
        assert gaps.mean() == pytest.approx(1 / 250.0, rel=0.03)
        assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


def test_zipf_ranks_follow_theta():
    rng = np.random.default_rng(0)
    n, items, theta = 400_000, 1000, 0.99
    ranks = traffic.zipf_ranks(n, items, theta, rng)
    p = 1.0 / np.arange(1, items + 1) ** theta
    p /= p.sum()
    freq = np.bincount(ranks, minlength=items) / n
    assert np.allclose(freq[:5], p[:5], rtol=0.03)
    assert abs(freq.sum() - 1) < 1e-12


def test_corpus_blocks_cover_r_walks_per_node_in_degree_order():
    degrees = np.array([5, 1, 3, 2, 4, 1, 7, 2])
    mix = {"kind": "offline_corpus", "walks_per_node": 3, "block": 5,
           "order": "start_degree"}
    blocks = list(traffic.corpus_blocks(mix, degrees, 11))
    again = list(traffic.corpus_blocks(mix, degrees, 11))
    assert all(np.array_equal(a, b) for a, b in zip(blocks, again))
    allq = np.concatenate(blocks)
    assert np.bincount(allq, minlength=8).tolist() == [3] * 8
    for b in blocks:
        assert np.all(np.diff(degrees[b]) >= 0)
