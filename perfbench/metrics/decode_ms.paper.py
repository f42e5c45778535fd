"""The server's decode alone at the paper round's geometry, ms per call:
the median of the path driver's timed spans of back-to-back
``reconstruct_chunks`` calls, each span at least 250 ms of host clock."""
import statistics


def read(r):
    samples = r["measures"].get("decode_ms")
    return statistics.median(samples) if samples else None
