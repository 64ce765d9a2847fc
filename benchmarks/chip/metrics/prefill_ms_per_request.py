"""Engine time spent in prefill steps (the wall-domain ``prefill`` spans,
summed) per request admitted, in milliseconds."""


def read(rec):
    spans = rec["engine_spans"].get("prefill", [])
    admitted = rec["serve_metrics"].admitted
    return 1e3 * sum(spans) / admitted if spans and admitted else None
