#!/usr/bin/env python3
"""Nested host spans of a traced window, and what the device's idle
time went to.

    python3 chipbench/spantrace.py <trace dir>

reads the ``.xplane.pb`` that ``jax.profiler`` wrote under the trace dir
and prints one JSON object: every span that starts inside
``bench.window`` by name and by the name of its parent, the device's
idle time attributed piece by piece to the innermost span that covers
it (``host.other`` where none does), the longest idle gaps, each under
the name that covers most of it, and the engine's span readings.

The spans are the harness's ``bench.*``, the engine's ``engine.*`` and
``py.gc`` (DESIGN.md §12.3), and JAX's ``backend_compile*``; they nest
by time on each thread's line.  A trace is held as ``devtrace.py``
holds it, with each ``engine.*`` event's stats (its ids) kept.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402

KEEP = ("bench.", "engine.", "py.gc", "backend_compile")
NO_SPAN = "host.other"


def load(path: str) -> list:
    """The planes of the trace under ``path``: the host spans of
    ``KEEP`` on every thread line, and each TPU's ops."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {path}, found {files}")
    planes = []
    for plane in ProfileData.from_file(files[0]).planes:
        host = plane.name.startswith("/host:")
        if not host and not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        lines = []
        for line in plane.lines:
            if host:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns),
                        dict(e.stats) if e.name.startswith("engine.")
                        else {})
                       for e in line.events if e.name.startswith(KEEP)]
            elif line.name == devtrace.OPS_LINE:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns), {})
                       for e in line.events]
            else:
                continue
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


@dataclasses.dataclass
class Span:
    name: str
    start: float                 # ns
    end: float
    stats: dict
    parent: int                  # index into SpanTree.all, -1 at a root
    depth: int


@dataclasses.dataclass
class SpanTree:
    """The host spans of one traced window, nested, and the device's
    idle time by the span it fell under."""

    window_s: float
    all: list                    # every kept host span, each line nested
    spans: list                  # indices of those starting in the window
    idle_by_span: dict           # name -> idle seconds, mean over chips
    idle_gaps: list              # [(name, seconds)], longest first

    def __post_init__(self):
        self._window = set(self.spans)

    def _in(self, i: int, outer: str) -> bool:
        p = self.all[i].parent
        while p >= 0:
            if self.all[p].name == outer:
                return p in self._window
            p = self.all[p].parent
        return False

    def seconds(self, name: str, inside: str = None) -> tuple:
        """Count and seconds of the spans ``name`` in the window, or of
        those nested at any depth in a span ``inside`` that starts in
        the window too."""
        hit = [i for i in self.spans if self.all[i].name == name
               and (inside is None or self._in(i, inside))]
        return len(hit), sum(self.all[i].end - self.all[i].start
                             for i in hit) * 1e-9

    def stat_sum(self, name: str, stat: str) -> float:
        """The sum of one id over the spans ``name`` in the window."""
        return float(sum(self.all[i].stats.get(stat, 0) for i in self.spans
                         if self.all[i].name == name))

    def by_parent(self) -> dict:
        """name -> parent's name (``-`` at a root) -> [count, seconds]."""
        out: dict = {}
        for i in self.spans:
            s = self.all[i]
            up = self.all[s.parent].name if s.parent >= 0 else "-"
            c = out.setdefault(s.name, {}).setdefault(up, [0, 0.0])
            c[0] += 1
            c[1] += (s.end - s.start) * 1e-9
        return out


def _nest(events: list, out: list) -> None:
    """Append one line's spans to ``out``, each under the innermost
    earlier span that contains it."""
    stack: list = []
    for name, a, d, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]].end <= a:
            stack.pop()
        parent = stack[-1] if stack else -1
        out.append(Span(name, a, a + d, stats, parent, len(stack)))
        stack.append(len(out) - 1)


def _attribute(spans: list, gaps: list) -> list:
    """For each of the disjoint, sorted ``gaps``, the ns of it under each
    innermost covering span's name (``NO_SPAN`` under none)."""
    marks = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                   + [(s.end, 0, i) for i, s in enumerate(spans)])
    active: set = set()
    m, out = 0, []

    def advance(t):
        nonlocal m
        while m < len(marks) and marks[m][0] <= t:
            _, opens, i = marks[m]
            (active.add if opens else active.discard)(i)
            m += 1

    for a, b in gaps:
        advance(a)
        cover: dict = {}
        t = a
        while t < b:
            end = min(marks[m][0] if m < len(marks) else b, b)
            if active:
                i = max(active, key=lambda j: (spans[j].depth,
                                               spans[j].start))
                name = spans[i].name
            else:
                name = NO_SPAN
            cover[name] = cover.get(name, 0.0) + end - t
            t = end
            advance(t)
        out.append(cover)
    return out


def reduce(planes: list) -> SpanTree:
    """Nest the host spans and attribute the device's idle time."""
    host = [ln["events"] for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"]]
    windows = [e for evs in host for e in evs if e[0] == devtrace.WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {devtrace.WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    lo, hi = windows[0][1], windows[0][1] + windows[0][2]
    nodes: list = []
    for evs in host:
        _nest(evs, nodes)
    spans = [i for i, s in enumerate(nodes) if lo <= s.start < hi
             and s.name != devtrace.WINDOW_SPAN]
    attributable = [s for s in nodes if s.name != devtrace.WINDOW_SPAN
                    and s.start < hi and s.end > lo]

    devices = [p for p in planes if re.match(r"/device:TPU:\d+$", p["name"])]
    idle: dict = {}
    named: list = []
    for dev in devices:
        ops = [e for ln in dev["lines"] if ln["name"] == devtrace.OPS_LINE
               for e in ln["events"]]
        busy = devtrace._merge([(max(a, lo), min(a + d, hi))
                                for _, a, d, _ in ops
                                if min(a + d, hi) > max(a, lo)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for cover in _attribute(attributable, gaps):
            for name, ns in cover.items():
                idle[name] = idle.get(name, 0.0) + ns * 1e-9 / len(devices)
            name = max(cover, key=cover.get)
            named.append((name, sum(cover.values()) * 1e-9))
    named.sort(key=lambda g: -g[1])
    return SpanTree(window_s=(hi - lo) * 1e-9, all=nodes, spans=spans,
                    idle_by_span=idle, idle_gaps=named)


# -- the engine's span readings, None where their spans are absent ---------


def _per(num: float, den: float, scale: float):
    return num / den * scale if den else None


def step_host_ms(t: SpanTree):
    """Host ms per engine step: the step less its blocking fetches."""
    n, step = t.seconds("engine.step")
    _, fetch = t.seconds("engine.fetch", inside="engine.step")
    return _per(step - fetch, n, 1e3)


def fetch_wait_ms(t: SpanTree):
    """ms per engine step blocked on the device in the step's fetches."""
    n, _ = t.seconds("engine.step")
    return _per(t.seconds("engine.fetch", inside="engine.step")[1], n, 1e3)


def submit_us_per_event(t: SpanTree):
    """µs of ``submit`` per event handed in."""
    _, secs = t.seconds("engine.submit")
    return _per(secs, t.stat_sum("engine.submit", "n"), 1e6)


def forget_step_host_ms(t: SpanTree):
    """Host ms per engine step run by a forget, less its fetches."""
    n, step = t.seconds("engine.step", inside="engine.forget")
    _, fetch = t.seconds("engine.fetch", inside="engine.forget")
    return _per(step - fetch, n, 1e3)


def forget_fetch_ms(t: SpanTree):
    """ms per forget blocked on the device: the steps' fetches and the
    basket-count lookup."""
    n, _ = t.seconds("engine.forget")
    secs = (t.seconds("engine.fetch", inside="engine.forget")[1]
            + t.seconds("engine.forget.lookup")[1])
    return _per(secs, n, 1e3)


def forget_tail_ms(t: SpanTree):
    """ms per forget after its deletions: scrub, purge and residue."""
    n, _ = t.seconds("engine.forget")
    secs = sum(t.seconds(f"engine.forget.{p}")[1]
               for p in ("scrub", "purge", "residue"))
    return _per(secs, n, 1e3)


READINGS = {"step_host_ms.ingest": step_host_ms,
            "fetch_wait_ms.ingest": fetch_wait_ms,
            "submit_us_per_event.ingest": submit_us_per_event,
            "forget_step_host_ms": forget_step_host_ms,
            "forget_fetch_ms": forget_fetch_ms,
            "forget_tail_ms": forget_tail_ms}


def report(t: SpanTree, n: int = 10) -> dict:
    readings = {k: f(t) for k, f in READINGS.items()}
    return {"window_s": t.window_s, "spans": t.by_parent(),
            "idle_by_span": dict(sorted(t.idle_by_span.items(),
                                        key=lambda kv: -kv[1])),
            "idle_gaps": t.idle_gaps[:n],
            "readings": {k: v for k, v in readings.items()
                         if v is not None}}


if __name__ == "__main__":
    print(json.dumps(report(reduce(load(sys.argv[1])))))
