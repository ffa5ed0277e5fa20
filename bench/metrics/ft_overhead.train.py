"""The paper's headline, measured: step time with the cell's FT policy
over the same step with FT off, minus one, in %. Same weights and batch,
host clock with ``block_until_ready``, medians of three alternating rounds,
timed in the traced run before the profiler starts."""


def read(layer):
    t = layer.get("ft_timing")
    if not t:
        return None
    return 100.0 * (t["ft_on_step_s"] / t["ft_off_step_s"] - 1.0)
