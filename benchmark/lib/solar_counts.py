"""Closed-form parameter and byte counts of the hybrid KDA / gated-GQA
block with routed experts (``bigdl_tpu.models.hybrid_moe_lm``), from the
configuration file's constructor arguments alone;
tests/benchmark_tests/test_benchmark_solar.py holds them to the sizes of
the program's own trees. Kept with the benchmark so that no PR that claims
a gain can change the numerator of a utilization. The last function puts
the engine's counters into them."""

from . import spans

BF16 = 2
F32 = 4


def _dims(model):
    heads = model.get("kda_heads") or model["num_heads"]
    hd = model.get("kda_head_dim") or model["head_dim"]
    return {"d": model["d_model"], "q": model["num_heads"] * model["head_dim"],
            "kv": model["num_kv_heads"] * model["head_dim"],
            "kda_heads": heads, "kda_hd": hd, "kda": heads * hd,
            "rank": model.get("gate_rank") or hd,
            "conv": model.get("conv_kernel", 4),
            "width": model["expert_width"],
            "shared": model.get("shared_experts", 1) * model["expert_width"]}


def layer_kinds(model):
    """The mixer of every layer: a softmax layer (``gqa``) where
    ``l % (gqa_interval + 1) == 0``, KDA after it."""
    return ["kda" if l % (model["gqa_interval"] + 1) else "gqa"
            for l in range(model["num_layers"])]


def mixer_params(model, kind, matmul_only=False):
    """Parameters of one mixer; with ``matmul_only`` those that sit in a
    matrix multiplication (no conv tap, ``a_log``, ``dt_bias`` or norm)."""
    m = _dims(model)
    d = m["d"]
    if kind == "gqa":  # Wq, Wk, Wv, the output gate, Wo
        return d * m["q"] + 2 * d * m["kv"] + d * m["q"] + m["q"] * d
    n, r = m["kda"], m["rank"]
    mat = (3 * d * n + n * d              # w_qkv, wo
           + 2 * (d * r + r * n)          # the decay's and the gate's pairs
           + d * m["kda_heads"])          # wb
    rest = (m["conv"] * 3 * n + m["kda_heads"] + n + m["kda_hd"])
    return mat if matmul_only else mat + rest


def expert_params(model):
    """One routed expert: the SwiGLU's three matrices."""
    return 3 * model["d_model"] * model["expert_width"]


def beside_mixer_params(model, matmul_only=False):
    """What every layer holds beside its mixer and its routed experts:
    the shared expert, the router (with its selection bias) and two
    RMSNorm weights."""
    m = _dims(model)
    mat = 3 * m["d"] * m["shared"] + m["d"] * model["num_experts"]
    return mat if matmul_only else mat + model["num_experts"] + 2 * m["d"]


def params(model):
    """Every parameter this chip holds: the layers with ``experts_held``
    experts each, the final norm, embedding and head of ``vocab`` rows."""
    d = model["d_model"]
    return (2 * model["vocab"] * d + d
            + sum(mixer_params(model, kind) + beside_mixer_params(model)
                  + model["experts_held"] * expert_params(model)
                  for kind in layer_kinds(model)))


def step_weight_bytes(model):
    """Every matmul weight outside the routed experts once, and the head's
    rows, bf16 (the embedding is a gather of one row a slot)."""
    return BF16 * (model["vocab"] * model["d_model"]
                   + sum(mixer_params(model, kind, True)
                         + beside_mixer_params(model, True)
                         for kind in layer_kinds(model)))


def cache_row_bytes(model):
    """One position of one softmax layer's cache: K and V, bf16."""
    return 2 * _dims(model)["kv"] * BF16


def slot_bytes_by_kind(model, max_len):
    """What one decode slot holds, by kind of leaf."""
    m, kinds = _dims(model), layer_kinds(model)
    n_kda = kinds.count("kda")
    return {"kv_full": kinds.count("gqa") * cache_row_bytes(model) * max_len,
            "kda_state": n_kda * m["kda_heads"] * m["kda_hd"] ** 2 * F32,
            "conv_state": n_kda * (m["conv"] - 1) * 3 * m["kda"] * BF16}


def step_bytes(model, experts_touched, live_slots, live_positions):
    """The least bytes a bf16 decode step must move, by part: ``weights``
    (``step_weight_bytes``), ``experts`` (each held expert some live token
    chose, once), ``state`` (the live slots' KDA state and convolution
    history read and written), ``cache`` (the softmax layers' live
    rows)."""
    slot = slot_bytes_by_kind(model, 0)
    return {"weights": step_weight_bytes(model),
            "experts": experts_touched * BF16 * expert_params(model),
            "state": 2 * live_slots * (slot["kda_state"]
                                       + slot["conv_state"]),
            "cache": (layer_kinds(model).count("gqa")
                      * cache_row_bytes(model) * live_positions)}


def mean_step_bytes(model):
    """``step_bytes`` of a mean decode step of this process, from the
    engine's counters over its steps: held experts touched (summed over
    layers), live slots (tokens emitted) and live positions a step.
    ``None`` where a counter has counted nothing: a program without the
    routed layer's counters."""
    per_step = [spans.counter_ratio(name, "decode_steps_total")
                for name in ("moe_experts_touched_total",
                             "generated_tokens_total",
                             "decode_live_positions_total")]
    if not all(per_step):
        return None
    return step_bytes(model, *per_step)
