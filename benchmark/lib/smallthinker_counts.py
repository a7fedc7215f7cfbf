"""Closed-form parameter, byte and operation counts of the window / full
GQA block with an early router and ReGLU experts
(``bigdl_tpu.models.hybrid_moe_lm`` under SmallThinker's configuration),
from the configuration file's constructor arguments alone;
tests/benchmark_tests/test_benchmark_smallthinker.py holds them to the sizes
of the program's own trees. Kept with the benchmark so that no PR that
claims a gain can change the numerator of a utilization. The counts read
the work, whatever implements it: a prompt's real tokens and not its
bucket's, the band and not the blocks a kernel walks. The last functions
put the engine's counters and the traced slice's prefill spans into them."""

from . import spans

BF16 = 2
PREFILL_SPAN = "prefill_tokens_"  # the engine's span, the prompt's length after it


def _dims(model):
    return {"d": model["d_model"],
            "q": model["num_heads"] * model["head_dim"],
            "kv": model["num_kv_heads"] * model["head_dim"],
            "width": model["expert_width"]}


def layer_kinds(model):
    """``window`` where ``sliding_window_layout`` marks the layer, else
    ``global``."""
    return ["window" if w else "global"
            for w in model["sliding_window_layout"][:model["num_layers"]]]


def attention_params(model):
    """Wq, Wo and Wk, Wv of one layer: no bias, no gate, no q/k norm."""
    m = _dims(model)
    return 2 * m["d"] * m["q"] + 2 * m["d"] * m["kv"]


def expert_params(model):
    """One routed expert: the ReGLU's three matrices."""
    return 3 * model["d_model"] * model["expert_width"]


def layer_matmul_params(model, experts):
    """The matmul weights of one layer with ``experts`` experts counted:
    attention, the router, the experts."""
    return (attention_params(model)
            + model["d_model"] * model["num_experts"]
            + experts * expert_params(model))


def params(model):
    """Every parameter held: the layers with ``experts_held`` experts and
    two norms each, the final norm, embedding and head."""
    d = model["d_model"]
    return (2 * model["vocab"] * d + d + model["num_layers"] * (
        layer_matmul_params(model, model["experts_held"]) + 2 * d))


def cache_row_bytes(model):
    """One position of one layer's cache: K and V, bf16."""
    return 2 * _dims(model)["kv"] * BF16


def slot_bytes_by_kind(model, max_len):
    """What one decode slot holds, by kind of leaf: ``max_len`` rows a
    global layer, a ring of ``window`` rows a window layer."""
    kinds = layer_kinds(model)
    row = cache_row_bytes(model)
    return {"kv_full": kinds.count("global") * row * max_len,
            "kv_window": kinds.count("window") * row
            * min(model["window"], max_len)}


def step_bytes(model, experts_touched, live_positions, window_positions):
    """The least bytes a bf16 decode step must move, by part: ``weights``
    (attention and router of every layer and the head's rows, once; the
    embedding is a gather of one row a slot), ``experts`` (each expert
    some live token chose, once), ``cache`` (the live rows of the global
    layers' caches and min(pos, window) rows of every ring)."""
    kinds = layer_kinds(model)
    row = cache_row_bytes(model)
    return {"weights": BF16 * (model["vocab"] * model["d_model"]
                               + model["num_layers"]
                               * layer_matmul_params(model, 0)),
            "experts": experts_touched * BF16 * expert_params(model),
            "cache": row * (kinds.count("global") * live_positions
                            + kinds.count("window") * window_positions)}


def mean_step_bytes(model):
    """``step_bytes`` of a mean decode step of this process, from the
    engine's counters over its steps: experts touched (summed over
    layers), live positions and ring rows a step. ``None`` where a counter
    has counted nothing."""
    per_step = [spans.counter_ratio(name, "decode_steps_total")
                for name in ("moe_experts_touched_total",
                             "decode_live_positions_total",
                             "decode_window_positions_total")]
    if not all(per_step):
        return None
    return step_bytes(model, *per_step)


def attention_pairs(model, kind, s):
    """(query, key) pairs one layer of ``kind`` scores for a prompt of
    ``s`` tokens: the triangle, or past the window the band ``s * w -
    w^2 / 2``."""
    w = model["window"]
    if kind == "window" and s > w:
        return s * w - w * w / 2.0
    return s * s / 2.0


def window_band_flops(model, s):
    """Operations of the window layers' attention (QK^T and PV, 2 each a
    pair, head and dim) for one prompt of ``s`` tokens; 0 for a prompt
    the window covers (its prefill is the causal call)."""
    if s <= model["window"]:
        return 0.0
    return (layer_kinds(model).count("window") * 4.0 * _dims(model)["q"]
            * attention_pairs(model, "window", s))


def window_band_bytes(model, s):
    """Least bytes of those calls: q and the output at the query heads, k
    and v at the KV heads, once each, bf16."""
    if s <= model["window"]:
        return 0.0
    m = _dims(model)
    return (layer_kinds(model).count("window") * BF16 * s
            * (2 * m["q"] + 2 * m["kv"]))


def prefill_flops(model, s):
    """The model's operations for one prompt of ``s`` real tokens: 2 a
    parameter of the matmuls a token runs through (attention, router,
    ``top_k`` experts), the head once a prompt, attention by triangle and
    band."""
    m = _dims(model)
    return (2.0 * s * model["num_layers"]
            * layer_matmul_params(model, model["top_k"])
            + 2.0 * model["vocab"] * model["d_model"]
            + sum(4.0 * m["q"] * attention_pairs(model, kind, s)
                  for kind in layer_kinds(model)))


def slice_prompt_tokens(planes):
    """The real token count of every prefill that began and ended inside
    the traced slice: the engine names a span ``prefill_tokens_<n>``
    around each prefill program call. ``[]`` for a program without it."""
    out = []
    for events in spans.host_lines(planes):
        for name, _, _ in events:
            if name.startswith(spans.TAG + PREFILL_SPAN):
                out.append(int(name[len(spans.TAG + PREFILL_SPAN):]))
    return out
