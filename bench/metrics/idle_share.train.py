"""Share of the traced training window in which no operation ran on the
device (1 minus the union of op intervals, averaged over chips)."""


def read(rec):
    if rec["kind"] != "train":
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])
