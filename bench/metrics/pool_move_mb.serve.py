"""Megabytes the paged decode step moves in copies, slices and updates
that hold one layer's KV pool slab or more, as the program counts them
in its compiled step (``serve.pool_move_bytes`` on each ``serve.decode``
span), over the steps that start in the traced window. A step that
carries its pool through the layer scan and writes each token in place
reads 0. A program that keeps no such count reads ``None``."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "serve":
        return None
    got = program_spans.aligned(rec)
    if got is None:
        return None
    al, spans = got
    lo, hi = rec["trace"].window
    steps = [s.attrs["pool_move_bytes"] for s in spans
             if s.name == "serve.decode" and "pool_move_bytes" in s.attrs
             and lo <= s.t0_ns * 1e-9 + al.offset_s < hi]
    if not steps:
        return None
    return max(steps) / 1e6
