"""Reduce a profiler trace of the measured window to device numbers.

A trace here is a list of planes, each ``{"name", "lines": [{"name",
"events": [(name, start_ns, duration_ns, stats)]}]}`` (``load`` reads
one from the ``.xplane.pb`` JAX writes).  Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds the operations
and the ``XLA Modules`` line the programs they ran in.  The host's
``bench.*`` spans (``jax.profiler.TraceAnnotation`` in the harness)
are on the host plane; ``bench.window`` brackets the measured window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers take from one traced window."""

    window_s: float
    busy_s: float                # union of op intervals, mean over chips
    programs: dict               # program -> [device seconds, executions]
    ops: dict                    # "program/op" -> device seconds
    idle_gaps: list              # [(host span, seconds)], longest first
    spans: dict                  # host span -> [count, seconds]

    def program_seconds(self, pattern: str) -> tuple:
        """Device seconds and executions of programs matching ``pattern``."""
        rx = re.compile(pattern)
        secs = sum(v[0] for k, v in self.programs.items() if rx.search(k))
        runs = sum(v[1] for k, v in self.programs.items() if rx.search(k))
        return secs, runs

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose ``program/op`` matches."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.ops.items() if rx.search(k))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(path: str) -> list:
    """The planes of the ``.xplane.pb`` under ``path`` (a trace dir)."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {path}, found {files}")
    planes = []
    for plane in ProfileData.from_file(files[0]).planes:
        lines = []
        for line in plane.lines:
            if not (plane.name.startswith("/device:") and line.name in (
                    OPS_LINE, MODULES_LINE)) and not plane.name.startswith(
                    "/host:"):
                continue
            evs = []
            for e in line.events:
                if plane.name.startswith("/host:") and not e.name.startswith(
                        "bench."):
                    continue
                evs.append((e.name, float(e.start_ns), float(e.duration_ns),
                            {}))
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _op(name: str) -> str:
    """An op's name and result shape, without its operands (the trace
    names an op by its whole HLO instruction)."""
    return re.sub(r"^(\S+ = \S+).*$", r"\1", name)


def _program(name: str) -> str:
    """A program's stable name: the module name without its run id."""
    return re.sub(r"\(\d+\)$", "", name)


def _merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(planes: list) -> Reduced:
    """Busy and idle time, per-program and per-op device time, and the
    longest idle gaps by host span, inside ``bench.window``."""
    spans_ev = [e for p in planes if p["name"].startswith("/host:")
                for ln in p["lines"] for e in ln["events"]]
    windows = [e for e in spans_ev if e[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0][1], windows[0][1] + windows[0][2]
    host = sorted((e[1], e[1] + e[2], e[0]) for e in spans_ev
                  if e[0] != WINDOW_SPAN and e[1] < hi and e[1] + e[2] > lo)
    hstarts = [h[0] for h in host]
    spans: dict = {}
    for a, b, name in host:
        s = spans.setdefault(name, [0, 0.0])
        s[0] += 1
        s[1] += (min(b, hi) - max(a, lo)) * 1e-9

    devices = [p for p in planes if re.match(r"/device:TPU:\d+$", p["name"])]
    programs: dict = {}
    ops: dict = {}
    busy = 0.0
    gaps: list = []
    for dev in devices:
        lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
        mods = sorted((e[1], e[1] + e[2], _program(e[0]))
                      for e in lines.get(MODULES_LINE, [])
                      if lo <= e[1] < hi)
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            p = programs.setdefault(name, [0.0, 0])
            p[0] += (min(b, hi) - a) * 1e-9
            p[1] += 1
        intervals = []
        for name, start, dur, _ in lines.get(OPS_LINE, []):
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            intervals.append((a, b))
            i = bisect.bisect_right(starts, start) - 1
            prog = mods[i][2] if i >= 0 and start < mods[i][1] else "?"
            key = f"{prog}/{_op(name)}"
            ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        merged = _merge(intervals)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_doing(host, hstarts, a, b), (b - a) * 1e-9))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy / n,
                   programs=programs, ops=ops, idle_gaps=gaps, spans=spans)


def _doing(host: list, starts: list, a: float, b: float) -> str:
    """The host span that covers most of the gap ``[a, b)``.

    The harness's spans follow one another on one thread, so only the
    spans starting just before ``b`` can overlap the gap.
    """
    best, cover = "host.other", 0.0
    i = bisect.bisect_left(starts, b) - 1
    while i >= 0:
        s, e, name = host[i]
        c = min(e, b) - max(s, a)
        if c > cover:
            best, cover = name, c
        if e <= a:
            break
        i -= 1
    return best


def breakdown(red: Reduced, n: int = 10) -> dict:
    """The ``breakdown`` of a traced result line."""
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps[:n]]}
