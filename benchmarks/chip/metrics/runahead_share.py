"""Share of the decode steps that the serving loop ran ahead: dispatched
on the previous step's device tokens while that step was still in flight
(``ServeMetrics.decode_chained``), over the job's decode steps (its
wall-domain ``decode`` spans), in percent.  A program without run-ahead
has no such counter, and reads nothing."""


def read(rec):
    chained = getattr(rec["serve_metrics"], "decode_chained", None)
    steps = rec["decode_steps"]
    if chained is None or not steps:
        return None
    return 100.0 * chained / steps
