"""The benchmark is driven by data: every configuration, traffic mix and
per-layer metric is a file of its own, found by the name that
BENCHMARK.json gives it."""
import json
import re

import pytest

from benchpath import BENCH, ROOT, bench_module

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
run = bench_module("run")
traffic = bench_module("traffic")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_loads_its_configuration_and_traffic_by_name(cell):
    _, w, config, mix = run.load_cell(cell, rehearse=False)
    assert config["name"] == w["config"]
    assert mix == traffic.load(w["traffic"])
    assert (BENCH / "drivers" / f"{config['driver']}.py").is_file()
    e2e = run.cell_metrics(SPEC, w, trace=False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert run.cell_metrics(SPEC, w, trace=True)


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        path = BENCH / "metrics" / f"{m['name']}.py"
        assert path.is_file(), path
        # a reader finds nothing in an empty record and says so
        assert run.read_metric(m["name"], {}) is None


def test_names_units_and_moves():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_a_new_traffic_mix_is_a_new_file(tmp_path, monkeypatch):
    """A mix added as a file is found by name, with no code changed."""
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    (tmp_path / "poisson-flat.json").write_text(json.dumps(
        {"kind": "open_loop", "arrivals": "poisson", "rate_qps": 50,
         "zipf_theta": 0.0, "mix": {"deepwalk": 1.0}}))
    mix = traffic.load("poisson-flat")
    s = traffic.open_loop(mix, 100, 1, 0.0, 10.0)
    assert s["due"].size == 500 and s["programs"] == ["deepwalk"]
