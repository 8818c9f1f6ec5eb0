"""Distributed walks: queries hash-partitioned over devices (paper §6.6),
graph replicated per device, engine running under a data mesh.

Forces 8 host devices (run as a separate process — this script must be the
first thing to touch jax in the process).

    PYTHONPATH=src python examples/distributed_walks.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import EngineConfig, WalkEngine  # noqa: E402
from repro.graphs import power_law_graph  # noqa: E402
from repro.walks import node2vec  # noqa: E402


def main():
    devs = jax.devices()
    print(f"devices: {len(devs)} × {devs[0].platform}")
    graph = power_law_graph(10_000, 12, weight_dist="uniform", seed=0)
    engine = WalkEngine(graph, node2vec(), EngineConfig(method="adaptive"))

    Q = 1024
    starts = np.arange(Q, dtype=np.int32)
    # hash-partition queries over devices (paper's scheme — range mapping
    # scales worse because node ids correlate with degree)
    dev_of = starts % len(devs)
    order = np.argsort(dev_of, kind="stable")
    mesh = jax.make_mesh((len(devs),), ("data",),
                         axis_types=(AxisType.Auto,))
    sharded = jax.device_put(jnp.asarray(starts[order]),
                             NamedSharding(mesh, P("data")))

    t0 = time.time()
    paths, _ = engine.walk_batch(sharded, jax.random.key(0), 20)
    jax.block_until_ready(paths)
    print(f"{Q} walks × 20 steps on {len(devs)} devices: "
          f"{time.time() - t0:.2f}s (single-core host; on real hardware "
          f"this is embarrassingly parallel)")
    paths = np.asarray(paths)
    print("per-device query counts:",
          np.bincount(dev_of, minlength=len(devs)).tolist())
    print("all walks valid:", bool((paths >= 0).all()))


if __name__ == "__main__":
    main()
