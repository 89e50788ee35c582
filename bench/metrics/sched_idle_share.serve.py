"""Share of the traced serving window in which the device idled while
the engine admitted requests and assembled the next decode batch: idle
time under the program's ``serve.admit`` and ``serve.prepare`` spans,
lined up with the device trace (``benchlib/program_spans.py``)."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "serve":
        return None
    return program_spans.idle_share(rec, ("serve.admit", "serve.prepare"))
