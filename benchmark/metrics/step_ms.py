"""Median host-clock time of one whole Optimizer step (dispatch to loss
fetch) over the scored window, ms."""
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(run.get("step_ms"), 50)
