"""The prefill programs' share of the chip's peak, %: the model's
operations for the prompts the traced slice prefilled
(``lib/smallthinker_counts.py prefill_flops``: real tokens, not the
bucket's; 2 a parameter of the matmuls a token runs through, the head once
a prompt, attention by triangle and band) over peak bf16 FLOP/s times the
device seconds of the prefill programs in the slice. The prompts are those
of the engine's ``prefill_tokens_<n>`` spans; a program without the span
reads nothing. With the decode step's roofline it bounds the whole of the
cell's device time."""
from benchmark.lib import smallthinker_counts as counts, trace


def read(run):
    r = run["reduced"]
    if r is None or run["peaks"] is None:
        return None
    name = run["config"]["serve"]["programs"]["prefill"]
    seconds = trace.module_stats(r, name)[1]
    prompts = counts.slice_prompt_tokens(run.get("planes"))
    if not seconds or not prompts:
        return None
    flops = sum(counts.prefill_flops(run["config"]["model"], s)
                for s in prompts)
    return 100.0 * flops / (run["peaks"]["bf16_flops"] * seconds)
