"""Nested named wall-clock spans and counters: one ``Profiler`` per run,
shared by the policy, the executor and the mechanisms it drives.

- ``span(name)`` times a ``with`` block.  Per-name totals and counts
  accumulate whether or not the profiler is enabled (two
  ``perf_counter`` calls and two dict updates per span); span *records*,
  with their nesting depth, are kept only when ``enabled``.
- ``add(name, value)`` accumulates a counter (bytes, items) beside the
  spans; ``reset()`` clears totals, counts, counters and records.
- With ``annotate`` on, each span is also a
  ``jax.profiler.TraceAnnotation`` named ``repro:<span>``, so a profiler
  trace shows it on the host's clock beside the device's operations.

Spans sit at the grain of one mechanism's phase or one leaf of one
worker's state, never per chunk or per token.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

ANNOTATION_PREFIX = "repro:"


class _Span:
    """One live nested span; re-entered via ``with prof.span(name)``."""

    __slots__ = ("prof", "name", "t0", "depth", "ann")

    def __init__(self, prof: "Profiler", name: str):
        self.prof = prof
        self.name = name

    def __enter__(self) -> "_Span":
        p = self.prof
        self.depth = p._depth
        p._depth = self.depth + 1
        if p.annotate:
            from jax.profiler import TraceAnnotation
            self.ann = TraceAnnotation(ANNOTATION_PREFIX + self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        p = self.prof
        p._depth = self.depth
        p.totals[self.name] = p.totals.get(self.name, 0.0) + (t1 - self.t0)
        p.counts[self.name] = p.counts.get(self.name, 0) + 1
        if p.enabled:
            p.spans.append(
                (self.name, self.depth, p._anchor, p._anchor_wall, self.t0, t1)
            )


class Profiler:
    """Nested named wall-clock spans and counters.

    Totals (``total(name)``) accumulate whether or not the profiler is
    enabled — they back ``ElasticPolicy.gather_seconds`` /
    ``node_seconds``.  Span *records* (for Perfetto export and nesting
    checks) are only kept when ``enabled``; a disabled profiler records
    nothing and, with ``annotate`` off, annotates nothing.

    ``set_anchor(sim_time)`` pins the current simulated time so wall
    durations can be projected onto the simulation timeline at export.
    """

    def __init__(self, enabled: bool = False, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.counters: Dict[str, float] = {}
        # (name, depth, anchor_sim, anchor_wall, t0, t1)
        self.spans: List[Tuple[str, int, float, float, float, float]] = []
        self._depth = 0
        self._anchor = 0.0
        self._anchor_wall = 0.0

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def add(self, name: str, value: float) -> None:
        self.counters[name] = self.counters.get(name, 0) + value

    def total(self, name: str) -> float:
        return self.totals.get(name, 0.0)

    def set_anchor(self, sim_time: float) -> None:
        self._anchor = float(sim_time)
        self._anchor_wall = time.perf_counter()

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()
        self.counters.clear()
        self.spans.clear()
        self._depth = 0
