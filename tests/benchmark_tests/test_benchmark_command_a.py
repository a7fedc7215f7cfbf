"""What the ``command_a_plus_serve`` configuration brings: one rehearsal of
its cell, in one process, prints the contract line with prompts that outrun
the toy window and puts the five planted controls (the reference without
its window, with NeoX's rotation pairs, with a sequential block, with the
shared experts summed, on float8 weights) through the harness's own
comparison, each ``correct: false``; ``lib/command_a_counts.py`` equals the
sizes of the program's own trees at the published widths (4.733B
parameters, 88.1 MB a slot); the three new readers on a hand-written run,
and nothing where their input is missing; the manifest's entries and the
configuration's top-level keys against the catalog's row."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import command_a_counts as counts  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "command_a_plus_serve.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "rag_batch.json")))
MODEL = CONFIG["model"]
CELL = "serve_rag_batch"
CONTROLS = ["window_off_reference", "halfsplit_rope_reference",
            "serial_block_reference", "shared_sum_reference",
            "fp8_reference"]
NEW = ["cmda_decode_step_roofline", "cmda_prefill_mfu", "cmda_flash_roofline"]


def reader(base):
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + base, os.path.join(
            ROOT, "benchmark", "metrics", base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- rehearsal
@pytest.fixture(scope="module")
def rehearsal():
    """The cell at the rehearsal sizes with every control judged against
    the same program rows: (result line, checks, {control: its line})."""
    env = dict(os.environ, XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "controls",
                                      CELL + ".py"), ",".join(CONTROLS),
         "--seed", str(2 ** 31 + 5), "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()
             if ln.startswith("{")]
    checks, = [ln for ln in lines if ln.get("info") == "checks"]
    controls = {ln["control"]: ln for ln in lines
                if ln.get("info") == "control"}
    return lines[-1], checks, controls


# toy sizes, float32 (the configuration's rehearsal says why): the cell
# reads 3e-7 on every row, none flipped; the least of the controls, the
# rotation's pairs, 0.009
TOL = TRAFFIC["rehearsal"]["check"]


def test_rehearsal_prints_the_contract_line(rehearsal):
    line, checks, _ = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # every per-layer metric of the cell is on the line with no CPU number
    mine = {m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"prefill_pad_share.rag",
            "moe_experts_touched_share.rag"} <= set(line["metrics"]) <= mine
    assert all(m["value"] is None for m in line["metrics"].values())
    # prompt 40 past the toy window of 16, padded to the bucket of 64: the
    # rings have wrapped and `last` matters; 32 rows of the prompt's tail,
    # the engine's prefill row and 3 decode rows
    assert checks["logits_rel_err"] < TOL["rel_tol"] / 100
    assert checks["logits_rel_err_tail"] < TOL["rel_tol"] / 100
    assert checks["flipped_share"] == 0 and checks["rows"] == 32 + 4
    assert checks["logits_rel_err_all_rows"] == max(
        checks["logits_rel_err"], checks["logits_rel_err_tail"])
    assert 64 in checks["prefill_buckets"]


@pytest.mark.parametrize("control", CONTROLS)
def test_control_is_not_correct_by_the_harness_comparison(rehearsal,
                                                         control):
    _, _, controls = rehearsal
    got = controls[control]
    assert got["correct"] is False and got["rows"] == 36
    assert (got["logits_rel_err"] > 2 * TOL["rel_tol"]
            or got["flipped_share"] > 2 * TOL["flip_share_tol"])


# ------------------------------------------------------------------ counts
@pytest.fixture(scope="module")
def trees():
    """Shapes of the program's parameter tree and of one slot's cache at
    the published widths: ``eval_shape``, nothing is allocated."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import build_model
    model = build_model(CONFIG, attn_impl=None)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, MODEL["max_len"],
                                                    jnp.bfloat16))
    return model, params, cache


def test_parameter_counts_are_the_trees(trees):
    import jax
    model, params, cache = trees
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert counts.params(MODEL) == sum(v.size for _, v in leaves) \
        == 4_733_292_544
    ffn = params["layers"]["0"]["ffn"]
    assert counts.expert_params(MODEL) * 16 == ffn["w13"].size \
        + ffn["w2"].size
    assert counts.expert_params(MODEL) * 4 == ffn["shared_w13"].size \
        + ffn["shared_w2"].size
    # the step's weights: every matrix but the routed experts, once
    matrices = sum(v.size for path, v in leaves if v.ndim == 2)
    assert counts.step_bytes(MODEL, 0, 0, 0)["weights"] == 2 * matrices
    assert counts.slot_bytes_by_kind(MODEL, MODEL["max_len"]) \
        == model.cache_bytes_by_kind(cache) \
        == {"kv_full": 37_748_736, "kv_window": 50_331_648}


def test_prefill_operations_by_hand():
    """At the 8,192 bucket: 25.9 TFLOP of matmuls (3.16 GFLOP a token:
    attention, router, four shared experts and one routed expert on
    average, 8 x 16 / 128) and 7.2 of 128-head attention."""
    per_token = 2 * 4 * (142_606_336 + 524_288 + 5 * 50_331_648)
    band, triangle = 8192 * 4096 - 4096 ** 2 / 2, 8192 ** 2 / 2
    attention = 4.0 * 16384 * (3 * band + triangle)
    assert counts.flash_flops(MODEL, 8192) == attention
    assert counts.prefill_flops(MODEL, 8192) == pytest.approx(
        8192 * per_token + 2.0 * 32768 * 4096 + attention)
    assert 25.8e12 < 8192 * per_token < 25.9e12
    assert 7.1e12 < attention < 7.2e12


# ----------------------------------------------------------------- readers
@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def made_up_run(prompts=(6144, 8064), **over):
    """A reduced trace by hand: five steps in 0.1 s, the prefills 0.5 s,
    the flash kernels 0.1 s, the engine's prefill spans on a host line."""
    events = [("bigdl:decode_prefill", 0, 10)] + [
        (f"bigdl:prefill_tokens_{s}", i, 5) for i, s in enumerate(prompts)]
    run = {"config": CONFIG, "peaks": PEAKS,
           "planes": [{"name": "/host:CPU", "lines": [
               {"name": "t", "events": events}]}],
           "reduced": {
               "op_seconds": {"flash_fwd_window bf16[128,8192,128]": 0.06,
                              "flash_fwd bf16[128,8192,128]": 0.04,
                              "gmm bf16[16,4096,8192]": 0.3},
               "modules": {
                   "jit__one(7)": {"count": 5.0, "seconds": 0.1},
                   "jit__prefill(9)": {"count": 2.0, "seconds": 0.5}}}}
    return dict(run, **over)


def count_steps(reg):
    reg.counter("decode_steps_total").inc(10)
    reg.counter("decode_live_positions_total").inc(10 * 200_000)
    reg.counter("decode_window_positions_total").inc(10 * 130_000)
    reg.counter("moe_experts_touched_total").inc(10 * 56)


def test_readers_on_a_made_up_run(registry):
    count_steps(registry)
    run = made_up_run()
    parts = counts.step_bytes(MODEL, 56, 200_000, 130_000)
    assert reader("cmda_decode_step_roofline").read(run) == pytest.approx(
        100 * sum(parts.values()) / 819e9 / 0.02)
    least = sum(max(counts.flash_flops(MODEL, s) / 197e12,
                    counts.flash_bytes(MODEL, s) / 819e9)
                for s in (6144, 8064))
    assert reader("cmda_flash_roofline").read(run) == pytest.approx(
        100 * least / 0.1)
    flops = sum(counts.prefill_flops(MODEL, s) for s in (6144, 8064))
    assert reader("cmda_prefill_mfu").read(run) == pytest.approx(
        100 * flops / (197e12 * 0.5))


@pytest.mark.parametrize("base", NEW)
@pytest.mark.parametrize("why,over", [
    ("no trace", {"reduced": None}),
    ("no peak table (the rehearsal)", {"peaks": None}),
    ("nothing of it on the trace",
     {"reduced": {"modules": {}, "op_seconds": {}}})])
def test_a_reader_reads_nothing_without_its_input(registry, base, why,
                                                  over):
    count_steps(registry)
    assert reader(base).read(made_up_run(**over)) is None, why


@pytest.mark.parametrize("base", NEW[1:])
def test_span_readers_need_the_prefill_spans(registry, base):
    assert reader(base).read(made_up_run(planes=[])) is None


def test_the_step_roofline_needs_the_counters(registry):
    assert reader(NEW[0]).read(made_up_run()) is None


# ---------------------------------------------------------------- manifest
def test_manifest_entries():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command_a_plus_serve", "rag_batch", 1)
    serve_tok_s, = [m for m in MANIFEST["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert CELL in serve_tok_s["workloads"]
    mine = [m for m in MANIFEST["per_layer"] if m["name"].endswith(".rag")]
    assert {m["name"].split(".")[0] for m in mine} == {
        "decode_step_ms", "prefill_time_share", "prefill_pad_share",
        "device_idle_share", "hbm_peak_gb", "moe_experts_touched_share",
        *NEW}
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in mine)


def test_top_level_keys_are_the_catalogs_row():
    """Every key of the catalog row's ``config`` at the file's top level,
    as published but the three counts ``reduced`` lists; ``published`` is
    the row itself; the model's arguments are the row's numbers, no width
    cut."""
    catalog = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
        "logit_scale": 1, "max_position_embeddings": 200000,
        "model_type": "cohere2_moe", "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 8, "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144}
    held = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    assert CONFIG["published"] == catalog
    assert {k: CONFIG[k] for k in catalog} == dict(catalog, **held)
    assert CONFIG["reduced"] == list(held)
    assert CONFIG["source"] == ("https://huggingface.co/CohereLabs/"
                                "command-a-plus-05-2026/blob/main/"
                                "config.json")
    for arg, key in CONFIG["widths"].items():
        assert MODEL[arg] == held.get(key, catalog[key]), arg
    assert MODEL["num_experts"] == 128 and MODEL["sliding_window_layout"] \
        == MODEL["rope_layout"] == [1, 1, 1, 0]
    assert set(CONFIG["assumed"]) >= {"expert_width", "shared_average"}
