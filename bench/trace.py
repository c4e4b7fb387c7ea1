"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

- Device planes are those named ``/device:<KIND>:<n>``.  On each, the
  ``XLA Ops`` line holds one event per operation run on the device and
  the ``XLA Modules`` line one event per program run.
- The window is the host annotation ``bench:window``; every interval is
  clipped to it.
- Busy time is the union of the operation intervals; idle is the rest of
  the window.  ``busy_s`` is averaged over the device planes.
- A program's device time is the union of its module events, per run.
- Host spans are the ``bench:<name>`` annotations of ``probes.py``; an
  idle gap is labelled with the chain of spans, outermost first, that
  covers its midpoint on the host.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

PREFIX = "bench:"
WINDOW = PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:[A-Za-z]+:\d+$")
TOP = 10

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out



def _total(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def op_name(name: str) -> str:
    """``%fusion.574 = f32[..] fusion(..), kind=kOutput, ..`` ->
    ``fusion.574 fusion/kOutput``: the instruction and what it is."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][\w.\-]*)\(", " " + rest)
    kind = re.search(r"kind=(k\w+)", rest)
    what = (m.group(1) if m else "") + ("/" + kind.group(1) if kind else "")
    return f"{head.lstrip('%')} {what}".strip()


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time of each named event less the events nested inside it (a
    ``while`` holds its body's operations), summed by name."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [b - a for a, b, _ in evs]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    out: Dict[str, float] = defaultdict(float)
    for (_, _, name), t in zip(evs, own):
        out[name] += t
    return dict(out)


def _module_name(name: str) -> str:
    """``jit_train_step(123)`` -> ``jit_train_step``."""
    return name.split("(")[0]


class Reduced:
    """What one trace holds, in seconds."""

    def __init__(self, window: Interval, devices: Dict[str, Dict],
                 host_spans: List[Tuple[str, float, float]]):
        self.window = window
        self.devices = devices
        self.host_spans = host_spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> Optional[float]:
        busy = [_total(d["busy"]) for d in self.devices.values() if d["busy"]]
        return sum(busy) / len(busy) * 1e-9 if busy else None

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s
        return None if busy is None else 1.0 - busy / self.window_s

    def program_ms(self, module: str) -> Optional[float]:
        """Device time per run of program ``module`` on the first device
        that ran it: the union of its module events over the runs."""
        for d in self.devices.values():
            runs = d["modules"].get(module)
            if runs:
                return _total(union(runs)) / len(runs) * 1e-6
        return None

    def top_ops(self, n: int = TOP) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for name, dur in d["op_time"].items():
                tot[name] += dur
        k = max(len(self.devices), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k * 1e-9] for name, t in ranked]

    def idle_gaps(self, n: int = TOP) -> List[List]:
        """The longest idle gaps of the first device, each labelled by the
        host spans covering its midpoint."""
        d = next((d for d in self.devices.values() if d["busy"]), None)
        if d is None:
            return []
        lo, hi = self.window
        edges = [lo] + [x for iv in d["busy"] for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n]]

    def label(self, t: float) -> str:
        cover = [(s, e, name) for name, s, e in self.host_spans
                 if s <= t <= e and name != WINDOW]
        cover.sort(key=lambda c: (c[0], -c[1]))
        return "/".join(c[2][len(PREFIX):] for c in cover) or "none"


def reduce(pd) -> Reduced:
    host_spans: List[Tuple[str, float, float]] = []
    dev_planes = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    host_spans.append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW]
    if windows:
        window = windows[0]
    else:
        starts = [s for _, s, _ in host_spans] or [0.0]
        ends = [e for _, _, e in host_spans] or [0.0]
        window = (min(starts), max(ends))
    devices: Dict[str, Dict] = {}
    for plane in dev_planes:
        ops: List[Tuple[float, float, str]] = []
        modules: Dict[str, List[Interval]] = defaultdict(list)
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    if b <= window[0] or a >= window[1]:
                        continue
                    ops.append((max(a, window[0]), min(b, window[1]),
                                op_name(ev.name)))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    if b <= window[0] or a >= window[1]:
                        continue
                    modules[_module_name(ev.name)].append((a, b))
        devices[plane.name] = {
            "busy": union([(a, b) for a, b, _ in ops]),
            "op_time": self_times(ops),
            "modules": dict(modules),
        }
    return Reduced(window, devices, host_spans)
