"""The rings' part of the cache rows the decode steps had to read, %: 100 x
``decode_window_positions_total`` x window layers over (that +
``decode_live_positions_total`` x global layers), from the engine's
counters over the whole process. Past the window a ring's rows stop
growing and the share falls. Nothing without window layers or steps."""
from benchmark.lib import smallthinker_counts as counts, spans


def read(run):
    kinds = counts.layer_kinds(run["config"]["model"])
    ring = spans.counter_ratio("decode_window_positions_total",
                               "decode_steps_total")
    live = spans.counter_ratio("decode_live_positions_total",
                               "decode_steps_total")
    if not ring or not live:
        return None
    ring *= kinds.count("window")
    return 100.0 * ring / (ring + live * kinds.count("global"))
