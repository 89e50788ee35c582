"""Reduce a profiler trace to device busy time, collective exposure,
kernel time and the longest idle gaps.

A :class:`Trace` holds, per device, the intervals in which an XLA
operation ran, and the host spans the benchmark wrote with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``), all on the
profiler's one clock, in seconds. ``load`` reads the ``.xplane.pb`` file
that ``jax.profiler.stop_trace`` writes; the rest is plain arithmetic on
intervals, tested on constructed traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[float, float, str]

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# HLO collectives, and the async halves XLA splits them into, by the
# instruction's own name (never its operands')
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
OUTSIDE_SPANS = "host outside bench spans"


@dataclasses.dataclass
class Trace:
    """``ops`` names are the HLO instruction text as the trace gives it
    (``%fusion.7 = bf16[16,8192]{...} fusion(...), ...``)."""
    ops: Dict[str, List[Event]]       # device name -> leaf op events
    spans: List[Event]                # host spans written by the bench
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    xs, ys = merge(xs), merge(ys)
    i = j = 0
    out = []
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(events: Sequence[Event], window: Interval) -> List[Event]:
    lo, hi = window
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over devices."""
    if not tr.ops:
        return 0.0
    return sum(length([(a, b) for a, b, _ in evs])
               for evs in tr.ops.values()) / len(tr.ops)


def op_name(text: str) -> str:
    """``%fusion.7 = bf16[16,8192]{1,0} fusion(...)`` -> ``fusion.7``."""
    return text.split(" = ", 1)[0].lstrip("%")


def short_name(text: str) -> str:
    """The instruction's name and result shape, and a custom call's
    target: ``fusion.7 bf16[16,8192]``, ``closed_call.13
    bf16[16,1,24,128] tpu_custom_call``."""
    name = op_name(text)
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    m = re.match(r"([a-z0-9]+\[[0-9,]*\])", rest)
    if m:
        name += " " + m.group(1)
    t = re.search(r'custom_call_target="([^"]+)"', text)
    if t:
        name += " " + t.group(1)
    return name


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE.search(op_name(text)))


def exposed_collective_s(tr: Trace) -> float:
    """Seconds in which a collective runs on a device and no other
    operation does, averaged over devices."""
    if not tr.ops:
        return 0.0
    total = 0.0
    for evs in tr.ops.values():
        coll = [(a, b) for a, b, n in evs if is_collective(n)]
        comp = [(a, b) for a, b, n in evs if not is_collective(n)]
        total += length(coll) - length(intersect(coll, comp))
    return total / len(tr.ops)


def kernel_s(tr: Trace, pattern: str) -> Tuple[float, int]:
    """Summed device time of the ops whose name matches ``pattern``, over
    all devices, and how many such events there were."""
    rx = re.compile(pattern)
    secs, n = 0.0, 0
    for evs in tr.ops.values():
        for a, b, name in evs:
            if rx.search(name):
                secs += b - a
                n += 1
    return secs, n


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` ops (by :func:`short_name`) that took the most device
    time, in seconds per device."""
    by = defaultdict(float)
    for evs in tr.ops.values():
        for a, b, name in evs:
            by[short_name(name)] += b - a
    n = max(len(tr.ops), 1)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs / n] for name, secs in ranked]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """Idle device time by what the host was doing: each part of a gap
    between busy intervals goes to the bench span that covers it, the
    rest to host work outside the bench's spans; seconds per device,
    the ``k`` largest."""
    by = defaultdict(float)
    spans = [(a, b, n) for a, b, n in tr.spans if n != WINDOW_SPAN]
    lo, hi = tr.window
    for evs in tr.ops.values():
        busy = merge([(a, b) for a, b, _ in evs])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = []
            for a, b, name in spans:
                part = intersect([(g0, g1)], [(a, b)])
                if part:
                    by[name] += length(part)
                    covered += part
            by[OUTSIDE_SPANS] += (g1 - g0) - length(covered)
    n = max(len(tr.ops), 1)
    ranked = sorted(((name, secs) for name, secs in by.items() if secs > 0),
                    key=lambda kv: -kv[1])[:k]
    return [[name, secs / n] for name, secs in ranked]


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that hold no other: on the ops line a ``while`` or a
    call spans the ops of its body, which would count twice."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (a, b, name) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][0] < b and evs[i + 1][1] <= b:
            continue                      # the next one starts inside
        out.append((a, b, name))
    return out


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. Device planes
    are ``/device:TPU:<n>`` and their ops the leaf events of the ``XLA
    Ops`` line; host spans are events named ``bench.*`` on any host
    line. The window is the ``bench.window`` span, and every op is
    clipped to it."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    t = e.start_ns * 1e-9
                    evs.append((t, t + e.duration_ns * 1e-9, e.name))
            ops[plane.name] = leaves(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        t = e.start_ns * 1e-9
                        spans.append((t, t + e.duration_ns * 1e-9, e.name))
    win = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"trace under {trace_dir} has no "
                           f"{WINDOW_SPAN} span")
    window = win[0]
    return Trace(ops={d: clip(evs, window) for d, evs in ops.items()},
                 spans=clip(spans, window), window=window)
