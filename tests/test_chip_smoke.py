"""The chip smoke's own contract, checked without a chip: it refuses to
run where JAX finds no TPU, and its hop check rejects what is not a walk
of the graph."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.compile_cache import CHECKOUT_ROOT

SMOKE = CHECKOUT_ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(SMOKE)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


# a 4-node CSR: 0→{1,2}, 1→{2}, 2→{0,3}, 3→{}
INDPTR = np.array([0, 2, 3, 5, 5])
INDICES = np.array([1, 2, 2, 0, 3])


@pytest.mark.parametrize("paths,error", [
    ([[0, 1, 2, 3, -1], [2, 0, 2, 0, 1]], None),
    ([[0, 1, 2, 1, -1]], "not edges"),          # 2→1 is no edge
    ([[0, 2, -1, 0, 1]], "resumed"),            # walk restarts after -1
])
def test_check_hops(smoke, paths, error):
    paths = np.asarray(paths, np.int32)
    if error is None:
        assert smoke.check_hops(INDPTR, INDICES, paths) == int(
            (paths[:, 1:] >= 0).sum())
    else:
        with pytest.raises(AssertionError, match=error):
            smoke.check_hops(INDPTR, INDICES, paths)
