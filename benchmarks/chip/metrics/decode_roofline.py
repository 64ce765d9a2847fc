"""Decode programs' share of their roofline on the traced chips.

Least time of one decode step: the larger of its FLOPs over peak FLOP/s
and its bytes over peak bandwidth (the description's
``decode_step_bytes``: every weight but the embedding table, and the live
cache of the job's mean step), against the device time of the
``decode_fn`` program runs in the trace."""

import work


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["decode_runs"] or not rec["decode_steps"]:
        return None
    m, steps = rec["dims"], rec["decode_steps"]
    flops = rec["decode_flops"] / steps
    nbytes = rec["model"].decode_step_bytes(
        m, rec["max_batch"], rec["decode_positions"] / steps)
    t_min, _ = work.roofline_s(flops, nbytes, rec["peak"], m.chips)
    return 100.0 * t_min * tr["decode_runs"] / tr["decode_s"]
