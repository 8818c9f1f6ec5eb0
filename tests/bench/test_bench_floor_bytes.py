"""The floor byte count of a hop, on a hand-built graph."""
import numpy as np
import pytest

from benchpath import bench_module

fb = bench_module("floor_bytes")
reference = bench_module("reference")


def test_floor_bytes_by_hand():
    # 0 -> {1, 2, 3}; 1 -> {0}; 2 -> {0, 1, 3}; 3 -> {2}
    indptr = np.array([0, 3, 4, 7, 8])
    indices = np.array([1, 2, 3, 0, 0, 1, 3, 2])
    g = reference.Graph(indptr, indices, np.ones(8, np.float32))
    # a node2vec walk 0 -> 2 -> 3 -> 2: three hops, previous nodes
    # none, 0 (degree 3), 2 (degree 3)
    path = np.array([[0, 2, 3, 2]])
    _, _, prev, _, _ = reference.walk_hops(path)
    prev_deg = np.where(prev >= 0, g.deg[np.maximum(prev, 0)], -1)
    assert prev_deg.tolist() == [-1, 3, 3]
    # 20 bytes a hop; two hops with a previous node add its row bounds
    # (8) and ceil(log2(3 + 1)) = 2 index reads (8)
    assert fb.hop_floor_bytes("node2vec", prev_deg) == 3 * 20 + 2 * (8 + 8)
    assert fb.hop_floor_bytes("deepwalk", hops=3) == 60
    assert fb.hop_floor_bytes("ppr_nibble", hops=3) == 3 * 28


def test_floor_bytes_unknown_program_is_an_error():
    with pytest.raises(ValueError):
        fb.hop_floor_bytes("metapath", hops=1)
