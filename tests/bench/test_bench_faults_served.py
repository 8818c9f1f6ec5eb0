"""A served run with its timed path broken underneath must come out not
correct.  The harness runs as on the chip except for the look for the
chip (a CPU rehearsal at a tiny size), with the engine's epoch — what
the service runs for each tenant — broken in each way the cells can be:
a step that returns its state unchanged, half of the walker slots left
out, and a hop altered where it is produced.  The served configuration
is driven by each of its traffic files, found by name."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchpath import ROOT, bench_module

CONFIG = "ppr-serve-pl20"
TRAFFIC = ["poisson-zipf99", "burst2s-zipf99"]

run = bench_module("run")
traffic = bench_module("traffic")


def load_served(name, rehearse, short_waits=False):
    """(benchmark, cell, configuration, mix) of the served configuration
    under the traffic file ``name``, rehearsal-sized."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json")
                        .read_text())
    mix = traffic.load(name)
    config = run.merge(config, config["rehearse"])
    mix = run.merge(mix, mix["rehearse"])
    if short_waits:  # requests that never come: stop waiting for them
        config = dict(config, drain_s=2, prewarm_timeout_s=2)
    cell = {"name": f"{CONFIG}.{name}", "config": CONFIG, "traffic": name,
            "chips": 1}
    return bench, cell, config, mix


def unchanged(eng, state, new, emitted, stats):
    return state, jnp.full_like(emitted, -1), stats


def half_left_out(eng, state, new, emitted, stats):
    W = state.cur.shape[0]
    keep = jnp.arange(W) < W // 2

    def pick(n, o):
        return jnp.where(keep.reshape((W,) + (1,) * (n.ndim - 1)), n, o)

    mixed = jax.tree_util.tree_map(pick, new, state)
    return mixed, jnp.where(keep[None, :], emitted, -1), stats


def altered(eng, state, new, emitted, stats):
    V = eng.graph.num_nodes
    moved = jnp.where(emitted >= 0, (emitted + 1) % V, -1)
    cur = jnp.where(new.cur != state.cur, (new.cur + 1) % V, new.cur)
    return dataclasses.replace(new, cur=cur), moved, stats


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered])
@pytest.mark.parametrize("cell", TRAFFIC)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.core.runtime import WalkEngine
    real = WalkEngine.run_epoch_fn

    def broken(self, state, *a, **kw):
        new, emitted, stats = real(self, state, *a, **kw)
        return fault(self, state, new, emitted, stats)

    monkeypatch.setattr(WalkEngine, "run_epoch_fn", broken)
    monkeypatch.setattr(run, "load_cell", lambda name, rehearse: load_served(
        name, rehearse, short_waits=True))
    args = run.parse_args(["--workload", cell, "--seed", "4242",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", TRAFFIC)
def test_sound_timed_path_is_correct(cell, monkeypatch):
    monkeypatch.setattr(run, "load_cell", load_served)
    args = run.parse_args(["--workload", cell, "--seed", "4242",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args)
    assert result["correct"] is True, result["checks"]
