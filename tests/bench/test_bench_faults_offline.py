"""A run with its timed path broken underneath must come out not
correct.  The harness runs as on the chip except for the look for the
chip (a CPU rehearsal at a tiny size), with the engine's epoch — what
both the offline scheduler and the service run — broken in each way the
cells can be: a step that returns its state unchanged, half of the
walker slots left out, and a hop altered where it is produced."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchpath import ROOT, bench_module

CONFIG = "node2vec-pl20"

run = bench_module("run")

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
         if w["chips"] == 1 and w["config"] == CONFIG]


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
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.core.runtime import WalkEngine
    real = WalkEngine.run_epoch_fn

    def broken(self, state, *a, **kw):
        new, emitted, stats = real(self, state, *a, **kw)
        return fault(self, state, new, emitted, stats)

    monkeypatch.setattr(WalkEngine, "run_epoch_fn", broken)
    load = run.load_cell

    def short_waits(name, rehearse):
        bench, w, config, mix = load(name, rehearse)
        if "drain_s" in config:  # requests that never come: stop waiting
            config = dict(config, drain_s=2, prewarm_timeout_s=2)
        return bench, w, config, mix

    monkeypatch.setattr(run, "load_cell", short_waits)
    args = run.parse_args(["--workload", cell, "--seed", "4242",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_timed_path_is_correct(cell):
    args = run.parse_args(["--workload", cell, "--seed", "4242",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    """The harness's own verdict on the control: the same rule as
    ``correct``, with the control's readings in the program's place.  At
    SCALE 14 the graph's hub rows are long enough for the bfloat16
    control to fail, while the program's run stays correct."""
    load = run.load_cell

    def scale14(name, rehearse):
        bench, w, config, mix = load(name, rehearse)
        config = run.merge(config, {"graph": {"scale": 14},
                                    "pit_sample": 8000})
        return bench, w, config, mix

    monkeypatch.setattr(run, "load_cell", scale14)
    args = run.parse_args(["--workload", cell, "--seed", "4243",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args, control=True)
    assert result["correct"] is True, result["checks"]
    assert result["control_correct"] is False, result["checks"]
