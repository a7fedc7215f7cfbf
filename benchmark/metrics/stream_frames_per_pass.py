"""How far the stream writer batches: ``stream_frames_total`` (SSE frames
written to streamed ``/generate`` responses) / ``stream_writer_passes_total``
(the writer's passes that wrote at least one), over the whole process.
One stream gives about one frame a pass; a round of 64 streams gives up
to 64. A program without the writer's counters reads nothing."""
from benchmark.lib import spans


def read(run):
    return spans.counter_ratio("stream_frames_total",
                               "stream_writer_passes_total")
