"""Share of the rows a train step computes that are padding: the
capacity plan's buffer rows against the global batch (a count)."""


def read(rec):
    if rec["kind"] != "train" or not rec["rows_by_device"]:
        return None
    rows = sum(rec["rows_by_device"].values())
    return 100.0 * (1.0 - rec["mix"]["global_batch"] / rows)
