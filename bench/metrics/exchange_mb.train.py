"""Megabytes one chip's collectives put out in one train step, as the
program counts them in its compiled step (``train.exchange_bytes`` on
each ``train.step`` span; FSDP gathers, the gradient reduction, the
vocabulary-parallel embedding and head), over the steps that start in
the traced window. A program that keeps no such count reads ``None``."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "train":
        return None
    got = program_spans.aligned(rec)
    if got is None:
        return None
    al, spans = got
    lo, hi = rec["trace"].window
    steps = [s.attrs["exchange_bytes"] for s in spans
             if s.name == "train.step" and "exchange_bytes" in s.attrs
             and lo <= s.t0_ns * 1e-9 + al.offset_s < hi]
    if not steps:
        return None
    return max(steps) / 1e6
