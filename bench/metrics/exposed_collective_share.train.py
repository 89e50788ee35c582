"""Share of the traced training window in which a collective runs on a
device and no other operation does, averaged over the chips. Only a
cell on several chips has an exchange to read."""
from benchlib import trace


def read(rec):
    if rec["kind"] != "train" or rec["chips"] < 2:
        return None
    return 100.0 * trace.exposed_collective_s(rec["trace"]) \
        / rec["window_s"]
