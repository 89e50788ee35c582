"""The program's own spans (``repro.obs``), lined up with the device trace.

The program keeps its spans in memory on ``time.perf_counter_ns``; the
trace is on the profiler's clock, which counts from the start of the
profiling session. The two are lined up through the calls that both
record: each traced ``bench.*`` span of a step encloses exactly one
program call span of the same step (``serve.decode``,
``train.step``). :func:`align` pairs them in order and takes the
median of their start differences as the offset from the program's
clock to the trace's; :func:`idle_by_span` then hands each idle piece
of the device to the innermost program span that covers it.

A program without ``repro.obs`` has no spans: every reader built on
this module then reads ``None``.
"""
from __future__ import annotations

import dataclasses
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import trace as trace_mod
from benchlib.train_cell import WARM_STEPS

SLACK_S = 0.5e-3        # a harness span may poke out of its program span
OUTSIDE = "outside program spans"

# per traffic kind: the program call span, the harness span that encloses
# each such call, and the step of the first call inside the traced window
# (serving: the window opens at the backlog run's first decode completion)
CALLS = {"serve": ("serve.decode", "bench.decode_step", 2),
         "train": ("train.step", "bench.train_step", WARM_STEPS + 1)}


@dataclasses.dataclass
class Alignment:
    offset_s: float     # trace time = program time (s) + offset_s
    pairs: int          # harness spans paired with program spans
    worst_s: float      # largest poke of a harness span out of its pair


def program_spans():
    """The program's spans, or ``None`` where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans()


def _s(ns: int) -> float:
    return ns * 1e-9


def align(tr: trace_mod.Trace, spans: Sequence, call: str,
          harness_span: str, first_step: int) -> Optional[Alignment]:
    """Pair the traced ``harness_span`` spans, in order, with the
    ``call`` spans of the last run that holds step ``first_step``, from
    that step on; ``None`` where they cannot be paired or any harness
    span lies outside its program span by more than ``SLACK_S``."""
    calls = [s for s in spans if s.name == call]
    starts = [i for i, s in enumerate(calls)
              if s.attrs.get("step") == first_step]
    harness = sorted((a, b) for a, b, n in tr.spans if n == harness_span)
    if not starts or not harness:
        return None
    prog = calls[starts[-1]:starts[-1] + len(harness)]
    if len(prog) < len(harness) or any(
            p.attrs.get("step") != first_step + i
            for i, p in enumerate(prog)):
        return None
    offset = statistics.median(h[0] - _s(p.t0_ns)
                               for h, p in zip(harness, prog))
    worst = max(max(_s(p.t0_ns) + offset - a, b - _s(p.t1_ns) - offset)
                for (a, b), p in zip(harness, prog))
    if worst > SLACK_S:
        return None
    return Alignment(offset_s=offset, pairs=len(harness), worst_s=worst)


def _owners(spans: Sequence, offset: float, window: Tuple[float, float]
            ) -> List[Tuple[float, float, str]]:
    """The window cut into pieces, each named after the innermost
    program span covering it (``OUTSIDE`` where none does)."""
    lo, hi = window
    ivs = [(max(_s(s.t0_ns) + offset, lo), min(_s(s.t1_ns) + offset, hi),
            s) for s in spans]
    ivs = [(a, b, s) for a, b, s in ivs if b > a]
    ids = {s.id: s for _, _, s in ivs}
    depth: Dict[int, int] = {}

    def depth_of(s) -> int:
        if s.id not in depth:
            parent = ids.get(s.parent)
            depth[s.id] = 0 if parent is None else depth_of(parent) + 1
        return depth[s.id]

    points = sorted({lo, hi} | {x for a, b, _ in ivs for x in (a, b)})
    ivs.sort(key=lambda v: v[0])
    out: List[Tuple[float, float, str]] = []
    active: List[tuple] = []
    k = 0
    for x0, x1 in zip(points, points[1:]):
        while k < len(ivs) and ivs[k][0] <= x0:
            active.append(ivs[k])
            k += 1
        active = [v for v in active if v[1] > x0]
        name = (max(active, key=lambda v: (depth_of(v[2]), v[0]))[2].name
                if active else OUTSIDE)
        if out and out[-1][2] == name and out[-1][1] == x0:
            out[-1] = (out[-1][0], x1, name)
        else:
            out.append((x0, x1, name))
    return out


def idle_by_span(tr: trace_mod.Trace, spans: Sequence, offset: float
                 ) -> Dict[str, float]:
    """Device-idle seconds in the traced window by the innermost program
    span covering them (``OUTSIDE`` for the rest), averaged over the
    devices; empty where the trace holds no device."""
    owners = _owners(spans, offset, tr.window)
    lo, hi = tr.window
    by: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        busy = trace_mod.merge([(a, b) for a, b, _ in evs])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        i = j = 0
        while i < len(idle) and j < len(owners):
            a = max(idle[i][0], owners[j][0])
            b = min(idle[i][1], owners[j][1])
            if b > a:
                by[owners[j][2]] += b - a
            if idle[i][1] < owners[j][1]:
                i += 1
            else:
                j += 1
    n = max(len(tr.ops), 1)
    return {name: secs / n for name, secs in by.items()}


def aligned(rec) -> Optional[Tuple[Alignment, list]]:
    """The alignment of a traced run's record and the program's spans."""
    spans = program_spans()
    if spans is None or rec["kind"] not in CALLS:
        return None
    al = align(rec["trace"], spans, *CALLS[rec["kind"]])
    return None if al is None else (al, spans)


def idle_table(rec) -> Optional[Dict[str, float]]:
    """:func:`idle_by_span` of a traced run's record."""
    got = aligned(rec)
    if got is None or not rec["trace"].ops:
        return None
    al, spans = got
    return idle_by_span(rec["trace"], spans, al.offset_s)


def idle_share(rec, names: Sequence[str]) -> Optional[float]:
    """Percent of the traced window in which the device idled under the
    program spans ``names``."""
    table = idle_table(rec)
    if table is None:
        return None
    return 100.0 * sum(table.get(n, 0.0) for n in names) / rec["window_s"]


def compiles(rec) -> Optional[int]:
    """Traces and compiles of jitted functions that program spans
    starting inside the traced window saw."""
    got = aligned(rec)
    if got is None:
        return None
    al, spans = got
    lo, hi = rec["trace"].window
    return sum(int(s.attrs.get("compiles", 0)) for s in spans
               if lo <= _s(s.t0_ns) + al.offset_s < hi)
