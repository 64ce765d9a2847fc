"""The serving loop's host time per decode step, in milliseconds: the
median over decode steps of the step's program spans outside the credit
wait (``decode.plan``, ``.dispatch``, ``.pull``, ``.account``,
``.retire``), summed.  Each decode step records one of each, in order, so
the i-th span of every name belongs to the i-th step.  A median, because
the harness starts and stops the profiler from a request's stamp: those
seconds fall inside two steps' ``decode.retire``."""

import statistics

PARTS = ("decode.plan", "decode.dispatch", "decode.pull", "decode.account",
         "decode.retire")


def read(rec):
    spans = rec["engine_spans"]
    if not spans.get("decode") or not all(p in spans for p in PARTS):
        return None
    steps = [sum(parts) for parts in zip(*(spans[p] for p in PARTS))]
    return 1e3 * statistics.median(steps)
