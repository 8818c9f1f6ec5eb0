"""The least number of HBM bytes a hop must move, whatever the sampler.

Given the graph in CSR form, any exact sampler has to, for each hop out
of v:

* read v's row bounds, ``indptr[v]`` and ``indptr[v+1]``: 2 x 4 bytes;
* read the chosen neighbour's id and its weight: 2 x 4 bytes (a sampler
  that never reads the chosen edge's weight cannot be exact for it);
* write the path entry: 4 bytes.

node2vec adds the test of whether the chosen candidate is an
out-neighbour of the previous node p: p's row bounds (8 bytes) and a
binary search of p's sorted row, ceil(log2(deg(p) + 1)) reads of 4
bytes; there is no previous node on a walk's first hop.  ppr_nibble adds
its residual mass, read and written once per hop: 2 x 4 bytes.

This never exceeds what an exact sampler moves (an alias or CDF table,
eRVS or eRJS all read at least these), so a share of the roofline built
on it cannot pass 100%, and it does not change when a change to the
program changes the sampler or the path.
"""
from __future__ import annotations

import numpy as np

WORD = 4
BASE = 5 * WORD  # row bounds (2), chosen id (1), its weight (1), path (1)


def hop_floor_bytes(kind: str, prev_degrees=None, hops: int = 0) -> float:
    """Floor bytes of ``hops`` hops of a program of ``kind``.  For
    node2vec pass ``prev_degrees``: the previous node's degree on each
    hop (-1 where the hop has no previous node)."""
    if kind == "node2vec":
        pd = np.asarray(prev_degrees, np.int64)
        has = pd >= 0
        search = np.ceil(np.log2(pd[has].astype(np.float64) + 1.0))
        return float(BASE * pd.size + has.sum() * 2 * WORD
                     + WORD * search.sum())
    if kind == "deepwalk":
        return float(BASE * hops)
    if kind == "ppr_nibble":
        return float((BASE + 2 * WORD) * hops)
    raise ValueError(f"no floor byte count for program kind {kind!r}")
