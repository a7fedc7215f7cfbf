"""The decode step's share of its memory roofline: the least seconds the
chip could take for the bytes any bf16 decode step has to read (every
matmul weight once at 2 bytes, and K and V of the cache positions that
were live: ``decode_live_positions_total`` / ``decode_steps_total``
positions a step, whole process, each 2 x num_kv_heads x head_dim x 2
bytes in every layer) at the peak HBM rate, over the device seconds of one
execution of the decode-step program, %. Memory-bound: two operations
for each 2-byte element read. A program without the counter reads
nothing."""
from benchmark.lib import flops, spans, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    live = spans.counter_ratio("decode_live_positions_total",
                               "decode_steps_total")
    name = run["config"]["serve"]["programs"]["decode_step"]
    count, seconds = trace.module_stats(r, name)
    if not live or not count:  # a counted step has a live position
        return None
    model = run["config"]["model"]
    head_dim = model["d_model"] // model["num_heads"]
    kv_bytes = 2 * model["num_kv_heads"] * head_dim * 2 * model["num_layers"]
    least = ((2 * flops.matmul_params(model) + live * kv_bytes)
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * count / seconds
