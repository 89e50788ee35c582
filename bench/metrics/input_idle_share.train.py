"""Share of the traced training window in which the device idled while
``train()`` waited for the loader's next batch and put it on the
devices: idle time under the program's ``train.input`` span, lined up
with the device trace (``benchlib/program_spans.py``)."""
from benchlib import program_spans


def read(rec):
    if rec["kind"] != "train":
        return None
    return program_spans.idle_share(rec, ("train.input",))
