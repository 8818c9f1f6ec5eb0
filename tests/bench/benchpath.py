"""Import helpers for the benchmark's tests: ``bench/`` is a directory
of scripts, not a package, so its modules are loaded by path."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(ROOT / "src") not in sys.path:
    sys.path.append(str(ROOT / "src"))


def bench_module(name: str):
    """``bench/<name>.py`` as a module (``bench/`` on sys.path, as
    ``bench/run.py`` puts it)."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    key = f"bench_{name}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
