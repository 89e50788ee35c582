"""Share of the traced serving window in which the device idled while
the engine brought logits to the host and sampled from them: idle time
under the program's ``serve.fetch`` and ``serve.sample`` spans, lined
up with the device trace (``benchlib/program_spans.py``)."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "serve":
        return None
    return program_spans.idle_share(rec, ("serve.fetch", "serve.sample"))
