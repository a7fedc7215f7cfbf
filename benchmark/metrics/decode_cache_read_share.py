"""The cache-and-state part of the bytes a SambaY decode step must move,
over all of them (``sambay_decode_step_roofline``'s numerator), %: how far
the step is from being bound by its weights alone. From the engine's
counters over the whole process; nothing without them."""
from benchmark.lib import sambay_counts


def read(run):
    least = sambay_counts.mean_step_bytes(run["config"]["model"])
    if least is None:
        return None
    weights, cache = least
    return 100.0 * cache / (weights + cache)
