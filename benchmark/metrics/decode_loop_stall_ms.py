"""How long the decode loop stood waiting for the engine lock behind
submitters' prefills: seconds of the program's ``bigdl:decode_lock_wait``
spans over the count of ``bigdl:decode_round`` in the traced slice, ms a
round."""
from benchmark.lib import spans


def read(run):
    rounds = spans.durations_ms(run.get("planes"), "decode_round")
    if not rounds:
        return None
    waits = spans.durations_ms(run.get("planes"), "decode_lock_wait")
    return sum(waits) / len(rounds)
