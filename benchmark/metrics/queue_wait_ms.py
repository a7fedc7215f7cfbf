"""Mean wait of a request that sat in the engine's queue, enqueue to
install, on the engine's clock: 1e3 x ``decode_queue_wait_seconds_total``
/ ``decode_queued_total``, over the whole process, ms."""
from benchmark.lib import spans


def read(run):
    wait_s = spans.counter_ratio("decode_queue_wait_seconds_total",
                                 "decode_queued_total")
    return None if wait_s is None else 1e3 * wait_s
