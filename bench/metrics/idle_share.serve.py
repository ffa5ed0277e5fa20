"""Device idle share of a serving window, in %: one minus the union of
device-op intervals over the traced part of the window, averaged over the
chips (`trace_reduce.Reduction.busy_s`)."""


def read(layer):
    red = layer.get("trace")
    if red is None or red.window_s <= 0 or not red.ops:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
