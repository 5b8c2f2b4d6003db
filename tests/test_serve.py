"""The serving entry point's workload ledger and service construction."""
import numpy as np
import pytest

from repro.launch.serve import EdgeLedger, build_service


@pytest.mark.parametrize("n_vertices,capacity", [(8, 0), (40, 10), (300, 0)])
def test_edge_ledger_tracks_a_set(n_vertices, capacity):
    """Deletes sampled from the ledger are live edges, inserts follow set
    semantics, and the swap-removal array and the hash set stay in step
    (including through rehashes when ``capacity`` is too small)."""
    rng = np.random.default_rng(n_vertices)
    src, dst = rng.integers(0, n_vertices, (2, 200)).astype(np.uint32)
    ledger = EdgeLedger(src, dst, capacity=capacity)
    live = set(zip(src.tolist(), dst.tolist()))
    assert len(ledger) == len(live)
    for _ in range(150):
        ds, dd = ledger.take(int(rng.integers(0, 30)), rng)
        taken = set(zip(ds.tolist(), dd.tolist()))
        assert len(taken) == len(ds) and taken <= live
        live -= taken
        ins = rng.integers(0, n_vertices, (int(rng.integers(0, 40)), 2))
        ins = ins.astype(np.uint32)
        new = set(map(tuple, ins.tolist())) - live
        assert ledger.add(ins[:, 0], ins[:, 1]) == len(new)
        live |= new
        s, d = ledger.edges()
        assert len(s) == len(live) == len(ledger)
        assert set(zip(s.tolist(), d.tolist())) == live
        q = rng.integers(0, n_vertices, (50, 2)).astype(np.uint32)
        assert np.array_equal(ledger.contains(q[:, 0], q[:, 1]),
                              [tuple(p) in live for p in q.tolist()])


def test_sharded_service_needs_one_device_per_shard():
    src = np.array([0, 1, 2], np.uint32)
    dst = np.array([1, 2, 0], np.uint32)
    with pytest.raises(ValueError, match="4 shards need 4 devices"):
        build_service(16, src, dst, shards=4, insert_budget=0)
