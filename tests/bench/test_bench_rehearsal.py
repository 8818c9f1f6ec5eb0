"""Each cell rehearsed at a tiny size through the benchmark's command on
the CPU: it checks everything a chip run checks and prints no result
line.  Off the chip, and beside no program, the command fails with no
result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchpath import ROOT

CHIPS = {w["name"]: w["chips"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
CELLS = list(CHIPS)


def bench(args, cwd=ROOT, timeout=600, chips=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if chips > 1:  # virtual CPU devices stand in for the chips
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.lstrip().startswith("{")]


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_checks_everything_and_prints_no_result(cell, trace):
    p = bench(["--workload", cell, "--seed", str(2**31 + 17), "--seconds",
               "2", "--trace", trace, "--rehearse"], chips=CHIPS[cell])
    assert p.returncode == 3, p.stderr[-3000:]
    assert result_lines(p.stdout) == []
    tail = p.stderr.strip().splitlines()
    assert "correct: True" in tail, p.stderr[-3000:]
    assert any(ln.startswith("check pit_ks:") for ln in tail)


def test_no_chip_no_result():
    p = bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0"], timeout=300)
    assert p.returncode != 0
    assert result_lines(p.stdout) == []
    assert "no TPU" in p.stderr


def test_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in spec["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    p = bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0", "--rehearse"], cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert result_lines(p.stdout) == []
