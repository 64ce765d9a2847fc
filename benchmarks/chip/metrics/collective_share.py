"""Share of the traced window in which a collective op (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) ran on a
chip: the union of its collective ops' intervals (``reduce.collective_ns``)
over the window, averaged over the chips, in percent.  One chip runs no
collective, and reads nothing."""

import statistics


def read(rec):
    tr = rec.get("trace")
    per_chip = (tr or {}).get("collective_s") or []
    if len(per_chip) < 2 or not tr.get("window_s"):
        return None
    return 100.0 * statistics.fmean(per_chip) / tr["window_s"]
