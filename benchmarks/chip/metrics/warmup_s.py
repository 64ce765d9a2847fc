"""Set-up's warm-up, in seconds: the program's wall-domain
``setup.warmup`` spans (one per prompt length: its compiles or
compile-cache loads and its first run), summed."""


def read(rec):
    spans = rec["engine_spans"].get("setup.warmup", [])
    return sum(spans) if spans else None
