"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``) beside
``BENCHMARK.json`` and ``bench/``.  The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the configuration names its driver
(``bench/drivers/<driver>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py`` — all found by name, so a new cell, mix or
metric is new files and new entries of ``BENCHMARK.json``.

The run needs a TPU with as many chips as the cell asks for; without it
it exits non-zero and prints no result.  It builds the system from
``--seed``, warms up every shape its traffic uses (set-up), measures for
``--seconds`` (with ``--trace 1`` under the profiler), compares what the
window produced with the plain reference (``bench/reference.py``), and
prints the numbers compared beside their limits as its last lines on
standard error and the result as one JSON line, last on standard output.
``--rehearse`` runs a tiny version of the cell on any backend (Pallas in
interpret mode off the chip): everything but the result line, exit 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
EXIT_NO_CHIP = 2
EXIT_REHEARSED = 3
COMPILES = None  # one compile counter per process (see harness)


class BenchError(RuntimeError):
    """A run that cannot produce a result (exit non-zero, no result)."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report the per-layer "
                         "metrics instead of the end-to-end ones")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny run on any backend; prints no result and "
                         "exits 3")
    return ap.parse_args(argv)


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys replaced, recursively."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool):
    """(benchmark, cell, configuration, traffic mix) for a cell name."""
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    bench = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; cells: "
                         f"{', '.join(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    sys.path.insert(0, str(BENCH))
    import traffic
    mix = traffic.load(cell["traffic"])
    if rehearse:
        config = merge(config, config.get("rehearse", {}))
        mix = merge(mix, mix.get("rehearse", {}))
    return bench, cell, config, mix


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metrics this cell reports: end-to-end with ``--trace 0``,
    per-layer with ``--trace 1``."""
    def applies(m):
        return cell["name"] in m.get("workloads", [cell["name"]])
    if not trace:
        return [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in bench["end_to_end"] if applies(m)}
    return [m for m in bench["per_layer"]
            if m["moves"] in moved and applies(m)]


def read_metric(name: str, record: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(record)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def open_devices(chips: int, rehearse: bool):
    """The JAX devices the cell runs on; a BenchError off the chip."""
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise BenchError(f"JAX found no TPU (platform "
                             f"{devs[0].platform!r}); the benchmark only "
                             f"runs on the chip")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX has "
                             f"{len(devs)}")
    return devs[:chips]


def device_peaks(kind: str, rehearse: bool):
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        if rehearse:
            return None
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def execute(args, control: bool = False):
    """Build, warm up, measure and check one run; returns the result
    dict (the caller prints it).  ``control`` also reads the control's
    numbers (``bench/control.py``): the checks named ``control.<name>``
    are the control's reading of check ``<name>``.  ``correct`` is the
    program's verdict; ``control_correct`` is the same rule with the
    control's readings put in the program's place."""
    bench, cell, config, mix = load_cell(args.workload, args.rehearse)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the program (src/repro) is not beside the "
                         f"benchmark at {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devs = open_devices(int(cell["chips"]), args.rehearse)
    peaks = device_peaks(devs[0].device_kind, args.rehearse)
    if not args.rehearse:
        # the program's own cache set-up, as its entry points make it
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    import harness
    global COMPILES
    if COMPILES is None:
        COMPILES = harness.CompileCounter()
    ctx = harness.Context(cell=cell, config=config, traffic=mix,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), rehearse=args.rehearse,
                          t_start=T_START, peaks=peaks, control=control,
                          compiles=COMPILES)
    spec = importlib.util.spec_from_file_location(
        f"bench_driver_{config['driver']}",
        BENCH / "drivers" / f"{config['driver']}.py")
    driver = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(driver)
    run = driver.run(ctx)

    metrics = {}
    units = {}
    for m in cell_metrics(bench, cell, bool(args.trace)):
        units[m["name"]] = m["unit"]
        if args.trace:
            v = read_metric(m["name"], run.record)
        elif m["name"] == "setup_s":
            v = run.setup_s
        else:
            v = run.e2e.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    own = {k: c for k, c in run.checks.items()
           if not k.startswith("control.")}
    correct = verdict(own)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": run.device_count,
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device}
    tr = run.record.get("trace")
    if args.trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"][:10],
                               "idle_gaps": tr["idle_gaps"][:10]}
    print(f"compiles in the window: "
          f"{run.record.get('compiles_in_window')}", file=sys.stderr)
    if "new_admit_sizes" in run.record:
        print(f"admission sizes warmed 1..{run.record['admit_sizes_warmed']}"
              f" (missed {run.record['admit_sizes_unwarmed']}); new in the "
              f"window: {run.record['new_admit_sizes']}", file=sys.stderr)
    if control:
        result["control_correct"] = verdict(dict(own, **{
            k[len("control."):]: c for k, c in run.checks.items()
            if k.startswith("control.")}))
    result["checks"] = {k: {"value": float(v), "limit": float(lim)}
                        for k, (v, lim) in run.checks.items()}
    return result


def verdict(checks: dict) -> bool:
    """Correct iff every number compared is within its limit."""
    return all(v <= lim for v, lim in checks.values())


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = execute(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP
    if args.rehearse:
        print(f"rehearsal: {json.dumps(result['metrics'])}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    # the numbers compared, beside their limits, end standard error
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        return EXIT_REHEARSED
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
