"""Bytes one decode step exchanges between chips, in MB (1e6 bytes): the
per-chip operand bytes of the collectives in the compiled decode program,
each weighted by the trip counts of the loops around it
(``ServeMetrics.decode_exchange_bytes``, read by the engine at warm-up).
One chip exchanges nothing, and a program without the counter has none:
both read nothing."""


def read(rec):
    exchanged = getattr(rec["serve_metrics"], "decode_exchange_bytes", None)
    return exchanged / 1e6 if exchanged else None
