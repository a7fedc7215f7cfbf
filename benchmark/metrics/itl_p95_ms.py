"""95th percentile of the gap between consecutive streamed tokens of a
request, at the client, over the scored window, ms: a prefill under the
engine lock stalls every slot, and shows here before it moves the median."""
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(run.get("token_gaps_ms"), 95)
