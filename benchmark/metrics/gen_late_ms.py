"""How late the load generator ran: 95th percentile of actual send time
minus due time over the scored requests, ms. A starved generator must not
be read as a fast server."""
from benchmark.lib.traffic import percentile


def read(run):
    return percentile(run.get("gen_late_ms"), 95)
