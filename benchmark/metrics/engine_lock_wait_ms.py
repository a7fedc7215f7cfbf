"""How long a submitter waited for the engine lock before its prefill:
median of the program's ``bigdl:submit_lock_wait`` spans in the traced
slice, ms."""
from benchmark.lib import spans
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(spans.durations_ms(run.get("planes"),
                                         "submit_lock_wait"), 50)
