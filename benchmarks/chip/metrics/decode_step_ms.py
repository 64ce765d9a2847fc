"""Median of the engine's wall-domain ``decode`` spans (one per decode
step: dispatch plus the blocking wait), in milliseconds."""

import statistics


def read(rec):
    spans = rec["engine_spans"].get("decode", [])
    return 1e3 * statistics.median(spans) if spans else None
