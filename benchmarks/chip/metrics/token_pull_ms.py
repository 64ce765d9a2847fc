"""Mean of the program's wall-domain ``decode.pull`` spans: the copy of a
decode step's next tokens to the host, in milliseconds."""

import statistics


def read(rec):
    spans = rec["engine_spans"].get("decode.pull", [])
    return 1e3 * statistics.fmean(spans) if spans else None
