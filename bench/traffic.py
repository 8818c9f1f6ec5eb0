"""One general generator for every traffic mix.

A mix is a data file under ``bench/traffic/`` (JSON), read by name.  Its
``kind`` picks one of two shapes:

* ``offline_corpus`` — r walks from every node of degree 1 or more, in
  a seeded random order,
  admitted in blocks; within a block the queries go in the order
  ``WalkEngine.run`` serves one call's queries (stable, by start degree).
* ``open_loop`` — requests due on a fixed schedule whatever the system
  does.  ``arrivals`` is ``poisson`` (a Poisson process at ``rate_qps``,
  drawn as a fixed count of uniform arrival times, so every seed offers
  the same number of requests in another order) or ``burst`` (every
  ``burst_period_s`` seconds, that period's ``rate_qps * period``
  requests at once).  Start nodes follow a Zipf law of exponent
  ``zipf_theta`` over a seeded permutation of the nodes a walk may start
  from (YCSB's zipfian); programs follow ``mix`` exactly, in a seeded
  order.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (run seed, purpose)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


# ------------------------------------------------------------- offline
def corpus_blocks(mix: dict, degrees: np.ndarray, seed: int):
    """Yield int32 start-node blocks in admission order (see module
    docstring); the corpus is ``walks_per_node`` walks from every node
    of degree 1 or more."""
    nodes = np.nonzero(degrees > 0)[0].astype(np.int32)
    total = nodes.size * int(mix["walks_per_node"])
    order = rng_for(seed, "corpus").permutation(total)
    block = int(mix["block"])
    for lo in range(0, total, block):
        starts = nodes[order[lo:lo + block] % nodes.size]
        if mix.get("order", "start_degree") == "start_degree":
            starts = starts[np.argsort(degrees[starts], kind="stable")]
        yield starts


# ----------------------------------------------------------- open loop
def zipf_ranks(n: int, num_items: int, theta: float,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` ranks in [0, num_items) with P(rank i) ∝ 1/(i+1)^theta."""
    w = 1.0 / np.arange(1, num_items + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      num_items - 1)


def open_loop(mix: dict, nodes, seed: int, start_s: float, end_s: float):
    """The schedule of every request due in [start_s, end_s), starting
    from ``nodes`` (an array of node ids, or a count ``V`` for all of
    ``range(V)``): a dict of
    ``due`` (seconds, ascending), ``start`` (int32 node) and ``program``
    (index into ``programs``), plus ``programs``."""
    rate = float(mix["rate_qps"])
    rng = rng_for(seed, "arrivals")
    span = end_s - start_s
    if mix["arrivals"] == "poisson":
        n = int(round(rate * span))
        due = start_s + np.sort(rng.random(n)) * span
    elif mix["arrivals"] == "burst":
        period = float(mix["burst_period_s"])
        per = int(round(rate * period))
        bursts = np.arange(start_s, end_s - 1e-9, period)
        due = np.repeat(bursts, per)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    n = due.shape[0]
    nodes = np.arange(nodes) if np.ndim(nodes) == 0 else \
        np.asarray(nodes)
    perm = rng_for(seed, "nodes").permutation(nodes)
    ranks = zipf_ranks(n, nodes.size, float(mix["zipf_theta"]),
                       rng_for(seed, "ranks"))
    programs = sorted(mix["mix"])
    share = np.asarray([float(mix["mix"][p]) for p in programs])
    counts = np.floor(share / share.sum() * n).astype(np.int64)
    counts[0] += n - counts.sum()
    prog = rng_for(seed, "programs").permutation(
        np.repeat(np.arange(len(programs)), counts))
    return {"due": due, "start": perm[ranks].astype(np.int32),
            "program": prog.astype(np.int32), "programs": programs}
