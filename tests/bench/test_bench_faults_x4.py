"""A four-chip cell whose chips' results are not exchanged must come out
not correct: the run is rehearsed on four virtual CPU devices in a child
process (the device count is fixed when JAX starts), with every chip's
lanes but the first left out of the epoch's results."""
import json
import os
import subprocess
import sys

import pytest

from benchpath import ROOT

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
         if w["chips"] == 4]


@pytest.mark.parametrize("cell", CELLS)
def test_chips_left_out_is_not_correct(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "x4_fault_child.py", cell],
                       cwd=ROOT / "tests" / "bench", env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
