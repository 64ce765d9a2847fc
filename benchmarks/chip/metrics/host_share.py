"""Share of the job's served time (first admission to last completion) in
which no engine step was being awaited: the serving loop's own host time
(scheduling, staging inputs, pulling tokens).  Engine steps are
``ServeMetrics.step_wall_s``: dispatch plus the blocking wait of every
prefill and decode."""


def read(rec):
    steps = rec["serve_metrics"].step_wall_s.total()
    if not steps:
        return None
    return 100.0 * (1.0 - steps / rec["job_s"])
