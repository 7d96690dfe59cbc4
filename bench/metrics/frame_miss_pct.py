"""Frame cache: share of frame lookups in the window that missed the LRU
and decoded the frame (``RangeQueryBatcher.stats``)."""


def read(run):
    miss, hit = run.counters.get("frames_decoded"), run.counters.get("frame_hits")
    if miss is None or miss + hit == 0:
        return None
    return 100.0 * miss / (miss + hit)
