"""Share of the serving window the host spends inside the engine's
``_admit`` (batch-1 prefill, KV scatter into pages, first sample), in %,
from the benchmark's own spans around it."""


def read(layer):
    w = layer.get("window_s")
    if not w:
        return None
    return 100.0 * layer["admit_s"] / w
