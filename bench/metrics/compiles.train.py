"""Jaxpr traces and executable builds of jitted functions inside the
traced training window, as the program's spans that start there count
them (``compiles``); the step is compiled before the window, so a sound
run reads 0."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "train":
        return None
    return program_spans.compiles(rec)
