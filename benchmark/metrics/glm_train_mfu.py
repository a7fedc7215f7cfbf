"""Model FLOP/s utilization of a GLM-4.7-Flash training cell: the cut's
operations a trained token requires (``lib/glm_counts.py
train_flops_per_token``: both heads, the MTP module, attention by the
causal half, and the routed rows a token costs on the experts held as the
model's training counters counted them, not their expected value;
recompute not counted) times tokens per second per chip over the chip's
published bf16 peak, %. A program without the counters reads nothing."""
from benchmark.lib import glm_counts as counts


def read(run):
    model = run["config"]["model"]
    tokens = (run.get("counted_steps") or 0) * run["seq_len"] * (
        run["batch"] // run["chips"])
    held = counts.held_rows_per_token(model, run.get("train_counters"),
                                      tokens)
    if run.get("tok_s_chip") is None or run["peaks"] is None or held is None:
        return None
    per_tok = counts.train_flops_per_token(model, run["seq_len"], held)
    return 100.0 * per_tok * run["tok_s_chip"] / run["peaks"]["bf16_flops"]
