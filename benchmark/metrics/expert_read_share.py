"""The routed experts' part of the bytes a hybrid MoE decode step must
move, over all of them (``solar_decode_step_roofline``'s numerator), %:
how far the step is bound by reading the weights of the experts its
tokens chose. From the engine's counters over the whole process; nothing
without them."""
from benchmark.lib import solar_counts


def read(run):
    least = solar_counts.mean_step_bytes(run["config"]["model"])
    if least is None:
        return None
    return 100.0 * least["experts"] / sum(least.values())
