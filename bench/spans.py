"""The program's ``obs`` spans, as intervals with their self time.

``repro.obs.trace`` records begin/end events per thread.  Here they become
one record per span: its name, start and end (integer nanoseconds of the
tracer's clock), its parent span and its self time, which is its duration
less the part that its direct children cover.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence


@dataclasses.dataclass
class Span:
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    child_ns: int = 0

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> int:
        return self.ns - self.child_ns


def from_events(events: Iterable[dict]) -> List[Span]:
    """Pair the ``B``/``E`` events of each thread into spans, in the order
    they opened; an unclosed span is dropped."""
    stacks: dict = {}
    opened: List[Span] = []
    closed = set()
    evs = [e for e in events if e.get("ph") in ("B", "E")]
    for ev in sorted(evs, key=lambda e: e["ts_ns"]):
        stack = stacks.setdefault(ev["tid"], [])
        if ev["ph"] == "B":
            span = Span(len(opened), ev["name"], ev["ts_ns"], ev["ts_ns"],
                        stack[-1].id if stack else None)
            opened.append(span)
            stack.append(span)
        elif stack and stack[-1].name == ev["name"]:
            span = stack.pop()
            span.end_ns = ev["ts_ns"]
            closed.add(span.id)
            if stack:
                stack[-1].child_ns += span.ns
    return [s for s in opened if s.id in closed]


def self_seconds(spans: Sequence[Span], name: str) -> List[float]:
    """Self time of each span called ``name``, in seconds."""
    return [s.self_ns / 1e9 for s in spans if s.name == name]


def children_seconds(spans: Sequence[Span], parent: str,
                     names: Sequence[str]) -> List[float]:
    """For each span called ``parent``, the seconds of its direct children
    called one of ``names``, summed."""
    totals = {s.id: 0 for s in spans if s.name == parent}
    for s in spans:
        if s.parent in totals and s.name in names:
            totals[s.parent] += s.ns
    return [totals[k] / 1e9 for k in sorted(totals)]
