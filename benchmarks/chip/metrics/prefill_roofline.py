"""Slot-prefill programs' share of their roofline on the traced chips.

Least time of a prefill: the larger of the useful FLOPs (the admitted rows'
prompts only, not the batch's idle rows) over peak FLOP/s and the bytes
(work.prefill_step_bytes) over peak bandwidth, summed over the prefills
that ran inside the traced window, against the device time of the
``slot_prefill_step`` program runs there."""

import work


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["prefill_runs"] or not tr["prefills"]:
        return None
    m = rec["dims"]
    t_min = sum(work.roofline_s(rows * work.prefill_flops(m, p),
                                work.prefill_step_bytes(m, rows, p),
                                rec["peak"], m.chips)[0]
                for rows, p in tr["prefills"])
    # A prefill cut by the window's edge is in the host's count and not
    # the device's, or the other way round: scale to the device's runs.
    t_min *= tr["prefill_runs"] / len(tr["prefills"])
    return 100.0 * t_min / tr["prefill_s"]
