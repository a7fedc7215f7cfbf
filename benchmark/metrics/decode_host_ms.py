"""The host's own share of a decode round (arguments, dispatch, emit,
hand-offs and their prefills): mean over the program's
``bigdl:decode_round`` spans of the round less the
``bigdl:decode_host_read`` inside it, where the host waits for the device,
ms."""
from benchmark.lib import spans


def read(run):
    return spans.mean(spans.self_ms(run.get("planes"), "decode_round",
                                    "decode_host_read"))
