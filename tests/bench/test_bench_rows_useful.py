"""The readers of the probe loops' ``rows`` counters: their share on a
synthetic window, and None where the program counts no rows."""
from __future__ import annotations

import pytest

from test_bench_counters import _probe, _read, _window


def _rows(obs, loop, hops, visits, width, rows, packs):
    _probe(obs, loop, hops, visits, width)
    obs.count(f"probe.{loop}.rows", rows)
    obs.count(f"probe.{loop}.packs", packs)


def _ingest(obs):
    for hops, visits, rows in ((4, 100, 160), (2, 28, 64)):
        with obs.span("store.query"):
            _rows(obs, "query", hops, visits, 64, rows, 1)
    with obs.span("store.apply.dispatch"):
        _rows(obs, "forward.insert", 3, 90, 32, 96, 0)
        _rows(obs, "transpose.insert", 1, 30, 32, 32, 0)


def _ingest_without_rows(obs):
    with obs.span("store.query"):
        _probe(obs, "query", 4, 100, 64)
    with obs.span("store.apply.dispatch"):
        _probe(obs, "forward.insert", 3, 90, 32)


CASES = {
    # 100 x (100 + 28) / (160 + 64)
    "query_rows_useful": 100.0 * 128 / 224,
    # 100 x (90 + 30) / (96 + 32)
    "update_rows_useful": 100.0 * 120 / 128,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_rows_reader_on_a_synthetic_window(name):
    assert _read(name, _window(_ingest)) == pytest.approx(CASES[name])


@pytest.mark.parametrize("name", sorted(CASES))
def test_rows_reader_is_none_without_rows_counters(name):
    assert _read(name, _window(_ingest_without_rows)) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_rows_reader_is_none_for_a_program_without_counters(name,
                                                           monkeypatch):
    from repro.obs import trace
    run = _window(_ingest)
    monkeypatch.delattr(trace, "span_counters")
    assert _read(name, run) is None
