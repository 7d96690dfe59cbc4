"""Frame decode: pyramid layers entropy-decoded in the window per TSBS
query (``RangeQueryBatcher.stats["layers_decoded"]``)."""


def read(run):
    n = len(run.counters.get("query_latency_ms", ()))
    return run.counters["layers_decoded"] / n if n else None
