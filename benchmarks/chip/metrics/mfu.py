"""Whole job's share of the chips' peak FLOP/s: model FLOPs of every
prompt and every decode step (the description's ``request_flops``) over
the job's served time (first admission to last completion, drain
included), the chips and the bf16 peak."""


def read(rec):
    m, model = rec["dims"], rec["model"]
    flops = sum(model.request_flops(m, p, g) for p, g in rec["lengths"])
    return 100.0 * flops / (rec["job_s"] * m.chips
                            * rec["peak"]["bf16_flops_per_s"])
