"""Fig. 15 — multi-device scalability of the walk engine.

One process sweeps ``jax.devices()[:n]`` for every device count the host
has (1, 2, 4, … up to ``len(jax.devices())``): the slot pool shards over a
1D walker mesh of the first ``n`` devices and the graph is replicated per
device (docs/scaling.md).  No child processes — a chip belongs to the one
process that opened it.  On a CPU host, give the process several devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before
launching; their times then say nothing about a chip.

Two rows per device count:

* ``fig15/devices{n}``       — ``walk_batch(devices=n)`` on one fully
  occupied batch (no host scheduling);
* ``fig15/sched_devices{n}`` — the *sharded streaming scheduler*
  (``run(devices=n)``): slot pool at half the query count, so every
  device takes mid-walk refills from the host queue.

Both must match the single-device run bit for bit (the topology-
invariance guarantee); a mismatch raises, so a failed phase exits
non-zero instead of printing a row.
"""
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core import EngineConfig, WalkEngine
from repro.graphs import power_law_graph
from repro.walks import node2vec

Q, STEPS = 512, 10


def _timed(fn):
    fn()  # warm (compile)
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def main(quick: bool = False):
    g = power_law_graph(2000, 12, weight_dist="uniform", seed=1)
    eng = WalkEngine(g, node2vec(), EngineConfig(method="ervs", tile=128))
    starts = np.arange(Q, dtype=np.int32)
    key = jax.random.key(0)
    n_max = len(jax.devices())
    counts = [n for n in (1, 2, 4, 8) if n <= n_max]
    if quick:
        counts = sorted({1, counts[-1]})
    ref_batch = ref_sched = None
    for n in counts:
        def batch():
            path, _ = eng.walk_batch(starts, key, STEPS, devices=n)
            return np.asarray(jax.block_until_ready(path))

        def sched():
            return eng.run(starts, num_steps=STEPS, key=key, batch=Q // 2,
                           epoch_len=4, devices=n)

        path, dt = _timed(batch)
        res, sched_dt = _timed(sched)
        if ref_batch is None:
            ref_batch, ref_sched = path, res.paths
        if not (np.array_equal(path, ref_batch)
                and np.array_equal(res.paths, ref_sched)):
            raise AssertionError(
                f"devices={n}: paths differ from the single-device run")
        dev_q = ([d["queries"] for d in res.per_device]
                 if res.per_device else [Q])
        balance = min(dev_q) / max(dev_q)
        where = f"{jax.devices()[0].platform}x{n}"
        emit(f"fig15/devices{n}", dt * 1e6, f"ident=True;{where}")
        emit(f"fig15/sched_devices{n}", sched_dt * 1e6,
             f"ident=True;balance={balance:.2f};{where}")


if __name__ == "__main__":
    main()
