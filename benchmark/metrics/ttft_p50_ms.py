"""Median first-token time from the due time, ms: the wait for the engine
lock, the request's own batch-1 prefill, and the decode step that emits its
first token."""
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(run.get("ttft_ms"), 50)
