"""Readings of the comparison that decides ``correct``, for the program
and for its control, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it runs the cell as ``bench/run.py`` does (a short window
at the cell's own load and sizes) and prints one JSON line with every
number compared: the program's, and the control's — the reference
computed in bfloat16 (weights, factors, the row's running sum, and
ppr_nibble's mass and threshold) put in the sampler's place at the same
positions.  ``correct`` is the program's verdict and ``control_correct``
the control's, by the same rule; the control has to come out not
correct.  The limits in the configuration are set between the two (see
PERF.md).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run_args = bench_run.parse_args(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"]
            + (["--rehearse"] if args.rehearse else []))
        try:
            res = bench_run.execute(run_args, control=True)
        except bench_run.BenchError as e:
            print(f"control: {e}", file=sys.stderr)
            return bench_run.EXIT_NO_CHIP
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "attempted": res["attempted"],
                          "checks": {k: c["value"] for k, c in
                                     res["checks"].items()},
                          "metrics": {k: m["value"] for k, m in
                                      res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
