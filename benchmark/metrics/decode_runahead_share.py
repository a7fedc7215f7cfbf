"""How often the decode loop runs ahead of its own emit: 100 x
``decode_runahead_steps_total`` (steps dispatched while an earlier step's
tokens were still unread) over ``decode_steps_total``, from the engine's
counters, over the whole process (the check's caller-driven steps and the
warm-up included), %. A program without the counter reads nothing."""
from benchmark.lib import spans

AHEAD = "decode_runahead_steps_total"


def read(run):
    from bigdl_tpu.obs.metrics import get_registry

    # what /metrics shows: asking the registry for a counter makes it
    if AHEAD not in get_registry().render():
        return None
    share = spans.counter_ratio(AHEAD, "decode_steps_total")
    return None if share is None else 100.0 * share
