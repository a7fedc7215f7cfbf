"""The host's own share of an Optimizer step (fetch, transfer, dispatch,
triggers): mean over the program's ``bigdl:train_step`` spans that fetched
a loss of the step less the ``bigdl:loss_fetch`` inside it, where the host
waits for the device, ms."""
from benchmark.lib import spans


def read(run):
    return spans.mean(spans.self_ms(run.get("planes"), "train_step",
                                    "loss_fetch"))
