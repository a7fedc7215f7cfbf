"""The hybrid MoE decode step's share of its memory roofline: the least
seconds the chip could take for the bytes any bf16 step has to move
(``lib/solar_counts.py``: every matmul weight outside the routed experts
and the head's rows once; each held expert that a live token chose, once;
the live slots' KDA state and convolution history read and written; the
softmax layers' live cache rows) at the peak HBM rate, over the device
seconds of one execution of the decode-step program, %. Experts touched,
slots and positions a step are the engine's counters over the whole
process. Memory-bound: at 64 tokens a step every weight read is used for
at most 128 operations an element. A program without the routed layer's
counters reads nothing."""
from benchmark.lib import solar_counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    name = run["config"]["serve"]["programs"]["decode_step"]
    count, seconds = trace.module_stats(r, name)
    least = solar_counts.mean_step_bytes(run["config"]["model"])
    if least is None or not count:
        return None
    return (100.0 * sum(least.values()) / run["peaks"]["hbm_bytes_per_s"]
            * count / seconds)
