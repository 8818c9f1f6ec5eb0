"""Child of ``test_bench_faults_x4.py``: one rehearsal run of a four-chip
cell on four virtual CPU devices (the parent sets ``XLA_FLAGS`` before
JAX starts), with the epoch's results from every chip but the first left
out — what a run would see if the exchange between chips were lost.
Prints the result's ``correct`` as its last line."""
import json
import sys

import jax
import jax.numpy as jnp

from benchpath import bench_module

run = bench_module("run")


def only_first_chip(state, new, emitted):
    n_dev = len(jax.devices())
    W = state.cur.shape[0]
    keep = jnp.arange(W) < W // n_dev

    def pick(n, o):
        return jnp.where(keep.reshape((W,) + (1,) * (n.ndim - 1)), n, o)

    mixed = jax.tree_util.tree_map(pick, new, state)
    return mixed, jnp.where(keep[None, :], emitted, -1)


def main(cell: str) -> int:
    from repro.core.runtime import WalkEngine
    real = WalkEngine.run_epoch_fn

    def broken(self, state, *a, **kw):
        new, emitted, stats = real(self, state, *a, **kw)
        new, emitted = only_first_chip(state, new, emitted)
        return new, emitted, stats

    WalkEngine.run_epoch_fn = broken
    args = run.parse_args(["--workload", cell, "--seed", "4244",
                           "--seconds", "2", "--trace", "0",
                           "--rehearse"])
    result = run.execute(args)
    print(json.dumps({"correct": result["correct"],
                      "checks": result["checks"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
