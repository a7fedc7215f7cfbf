"""Model FLOP/s utilization: the closed-form operations a trained token
requires (lib/flops.py; recompute not counted) times tokens per second per
chip over the chip's published bf16 peak, %."""
from benchmark.lib import flops


def read(run):
    if run.get("tok_s_chip") is None or run["peaks"] is None:
        return None
    per_tok = flops.train_flops_per_token(run["config"]["model"],
                                          run["seq_len"])
    return 100.0 * per_tok * run["tok_s_chip"] / run["peaks"]["bf16_flops"]
