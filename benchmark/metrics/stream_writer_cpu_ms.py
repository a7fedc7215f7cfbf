"""The stream writer's CPU a decode round: 1e3 x
``stream_writer_cpu_seconds_total`` (the one thread that writes every
streamed ``/generate``'s frames, its CPU over its passes) /
``decode_rounds_total``, over the whole process, ms. With
``stream_handler_cpu_ms`` it is what the streams cost the server. A
program without the writer's counter reads nothing."""
from benchmark.lib import spans

COUNTER = "stream_writer_cpu_seconds_total"


def read(run):
    from bigdl_tpu.obs.metrics import get_registry

    if COUNTER not in get_registry()._metrics:  # asking would make it
        return None
    cpu_s = spans.counter_ratio(COUNTER, "decode_rounds_total")
    return None if cpu_s is None else 1e3 * cpu_s
