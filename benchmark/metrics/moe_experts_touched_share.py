"""Held experts that at least one live token chose, over the experts held
(``experts_held`` x layers) a decode step, %: 100 x
``moe_experts_touched_total`` / (``decode_steps_total`` x held x layers),
from the engine's counters over the whole process. Even routing of 64
slots x 8 picks over 320 experts touches 80% of 40; skew lowers it. A
program without the counter reads nothing."""
from benchmark.lib import spans


def read(run):
    model = run["config"]["model"]
    touched = spans.counter_ratio("moe_experts_touched_total",
                                  "decode_steps_total")
    if not touched:
        return None
    return 100.0 * touched / (model["experts_held"] * model["num_layers"])
