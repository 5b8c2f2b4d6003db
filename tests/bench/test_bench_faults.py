"""The check fails what it must: each control, and a run whose timed path
is broken underneath (the chip look skipped, the rest of the run as is)."""
from __future__ import annotations

import numpy as np
import pytest

from benchkit import run_tiny


@pytest.mark.parametrize("cell,reading", [
    ("kron-t.ingest-t", "member_mismatch"),
    ("urand-t.ingest-t", "member_mismatch"),
    ("urand-t.fresh-t", "pagerank_l1")])
def test_control_fails_the_check(tiny_root, cell, reading):
    """The control's answers, put in the program's place, go through the
    harness's own check and its limits, and come out not correct."""
    from bench import control
    r = run_tiny(tiny_root, cell, control=control.answers)
    assert r["correct"]
    assert r["control"]["correct"] is False
    ctl = r["control"]["checks"]
    assert ctl[reading]["value"] > ctl[reading]["limit"]
    # the control's answers differ from the program's only where it reads
    assert all(c["value"] <= c["limit"] for k, c in ctl.items()
               if k != reading)


def _state_unchanged(monkeypatch):
    """The update step computes its answer but returns the views it was
    given."""
    import jax
    import jax.numpy as jnp

    from repro.stream import store
    real = store.update_views

    def unchanged(views, roles, ins, dels):
        copies = tuple(jax.tree.map(jnp.copy, v) for v in views)
        _, ins_mask, del_mask = real(copies, roles, ins, dels)
        return views, ins_mask, del_mask

    monkeypatch.setattr(store, "update_views", unchanged)


def _half_batch(monkeypatch):
    """Half of each update batch is left out."""
    from repro.stream import GraphStore
    real = GraphStore.apply

    def half(self, ins_src=None, ins_dst=None, ins_w=None, del_src=None,
             del_dst=None):
        cut = [None if a is None else np.asarray(a)[:len(a) // 2]
               for a in (ins_src, ins_dst, del_src, del_dst)]
        return real(self, cut[0], cut[1], ins_w, cut[2], cut[3])

    monkeypatch.setattr(GraphStore, "apply", half)


def _answer_altered(monkeypatch):
    """One membership answer flipped, and one vertex's component label
    changed, where each is produced."""
    from repro.algorithms import wcc
    from repro.stream import GraphStore
    real_query, real_wcc = GraphStore.query, wcc.wcc_static

    def query(self, src, dst):
        found = real_query(self, src, dst).copy()
        found[0] = ~found[0]
        return found

    def wcc_static(g, **kw):
        labels = real_wcc(g, **kw)
        return labels.at[0].set(labels.shape[0] - 1)

    monkeypatch.setattr(GraphStore, "query", query)
    monkeypatch.setattr(wcc, "wcc_static", wcc_static)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("cell", ["kron-t.ingest-t", "urand-t.fresh-t"])
def test_broken_timed_path_is_not_correct(tiny_root, cell, fault,
                                          monkeypatch):
    fault(monkeypatch)
    r = run_tiny(tiny_root, cell)
    assert not r["correct"]
    assert r["failed"] > 0 or any(c["value"] > c["limit"]
                                  for c in r["checks"].values())
