"""90th percentile of first-token time from the due time, ms (over ~90
scored requests it spreads by 7-17% from run to run, too wide for an
end-to-end bound: PERF.md)."""
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(run.get("ttft_ms"), 90)
