"""95th percentile, in ms, of every gap between consecutive output tokens
of every request in the window (the gaps that hold a batch-1 prefill of
another request make the tail)."""
from bench import traffic


def read(layer):
    gaps = layer.get("gaps_ms")
    if not gaps:
        return None
    return traffic.percentile(gaps, 95)
