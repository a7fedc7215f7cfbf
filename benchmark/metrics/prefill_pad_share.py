"""Share of prefilled positions that were padding: 100 x (1 -
``prompt_tokens_total`` / ``prefill_bucket_tokens_total``) from the
engine's counters, over the whole process (warm-up included), %."""
from benchmark.lib import spans


def read(run):
    real = spans.counter_ratio("prompt_tokens_total",
                               "prefill_bucket_tokens_total")
    return None if real is None else 100.0 * (1.0 - real)
