"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (1 - busy union / window)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
