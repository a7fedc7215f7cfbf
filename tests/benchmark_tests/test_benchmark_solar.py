"""What the ``solar_open2_serve`` configuration brings: its cell's
rehearsal prints the contract line, on weights drawn by the class's own
``init``; the cell's controls (the KDA state zeroed, the eighth expert
dropped, the reference on float8 weights) come out not correct by the
harness's own comparison, and ``flips`` reads the router's flips;
``lib/solar_counts.py`` equals the sizes of the program's own trees at the
published widths (3.31B parameters held, the issue's table); the three new
readers on a hand-written run, and nothing where their input is missing;
the manifest's entries, the configuration's top-level keys against the
catalog's row, and the traffic file, letter for letter."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import solar_counts as counts  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "solar_open2_serve.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "moe_batch.json")))
MODEL = CONFIG["model"]
CELL = "serve_moe_batch"


def reader(base):
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + base, os.path.join(
            ROOT, "benchmark", "metrics", base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- rehearsal
def rehearse(command, *more, seed=2 ** 31 + 5):
    """One rehearsal run -> (its result line, its ``checks`` line)."""
    env = dict(os.environ, XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *command), *more,
         "--seed", str(seed), "--seconds", "2", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    checks, = [json.loads(ln) for ln in lines
               if ln.startswith('{"info": "checks"')]
    return json.loads(lines[-1]), checks


# toy sizes, float32 (the configuration's rehearsal says why): 3e-6 to
# 6e-6 over seeds 2**31 + 5 and 12345; the controls read 0.97 to 1.13
REHEARSAL_TOL = TRAFFIC["rehearsal"]["check"]["rel_tol"]


def test_rehearsal_prints_the_contract_line():
    line, checks = rehearse(["run.py"], "--workload", CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # the counter metrics are on the line, and no CPU number under them
    assert {"prefill_pad_share.moe", "expert_read_share.moe",
            "moe_experts_touched_share.moe",
            "decode_runahead_share.moe"} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # prompt 20, padded to the bucket of 32, so `last` matters
    assert checks["logits_rel_err"] < REHEARSAL_TOL == 0.01
    assert 32 in checks["prefill_buckets"]


@pytest.mark.parametrize("control", ["kda_carry_zeroed", "top7",
                                     "fp8_reference"])
def test_control_is_not_correct_by_the_harness_comparison(control):
    line, checks = rehearse(["controls", CELL + ".py"], control,
                            "--trace", "0", seed=2 ** 31 + 11)
    assert line["correct"] is False and line["failed"] == 0
    assert checks["logits_rel_err"] > 10 * REHEARSAL_TOL
    assert checks["responses_exact"] and checks["greedy_tokens_in_vocab"]


def test_flips_control_reads_the_router_on_the_checks_tokens():
    line, checks = rehearse(["controls", CELL + ".py"], "flips",
                            "--trace", "0", seed=2 ** 31 + 99)
    assert line["correct"] is True and line["failed"] == 0
    # 20 tokens x 8 layers; in float32 the program chooses as the
    # reference does
    assert checks["router_pairs"] == 160
    assert checks["router_flipped_pairs"] == 0
    assert checks["logits_rel_err_positions"] < REHEARSAL_TOL
    assert checks["logits_rel_err_positions_routed_as_reference"] \
        == checks["logits_rel_err_positions"]


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
    _, params, _ = trees
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert counts.params(MODEL) == sum(v.size for _, v in leaves)
    assert 3.30e9 < counts.params(MODEL) < 3.32e9
    # in a matmul, outside the routed experts and the embedding: every
    # matrix but the conv taps
    mat = sum(v.size for path, v in leaves if len(v.shape) == 2
              and path[-1].key != "conv_w" and path[0].key != "emb")
    assert counts.step_weight_bytes(MODEL) == 2 * mat
    experts = sum(v.size for path, v in leaves if len(v.shape) == 3)
    assert experts == 4 * 40 * counts.expert_params(MODEL)


@pytest.mark.parametrize("what,millions", [
    ("kda", 137.7), ("gqa", 109.1), ("beside", 17.05), ("expert", 15.73)])
def test_sizes_are_the_issues_table(what, millions):
    got = {"kda": counts.mixer_params(MODEL, "kda"),
           "gqa": counts.mixer_params(MODEL, "gqa"),
           "beside": counts.beside_mixer_params(MODEL),
           "expert": counts.expert_params(MODEL)}[what]
    assert got / 1e6 == pytest.approx(millions, abs=0.06)
    assert counts.layer_kinds(MODEL) == ["gqa", "kda", "kda", "kda"]


def test_whole_model_is_250b_of_which_15b_a_token():
    """The published sizes from the same functions: 48 layers, 320
    experts, the whole vocabulary; 8 routed experts a token."""
    pub = CONFIG["published"]
    whole = dict(MODEL, num_layers=pub["num_hidden_layers"],
                 experts_held=pub["n_routed_experts"],
                 vocab=pub["vocab_size"])
    assert counts.params(whole) / 1e9 == pytest.approx(250.3, abs=0.1)
    active = (counts.params(whole) - 48 * (320 - 8)
              * counts.expert_params(whole))
    assert active / 1e9 == pytest.approx(14.7, abs=0.1)


def test_slot_bytes_are_the_cache_tree(trees):
    model, _, cache = trees
    assert counts.cache_row_bytes(MODEL) == 4096
    by_kind = counts.slot_bytes_by_kind(MODEL, MODEL["max_len"])
    assert by_kind == model.cache_bytes_by_kind(cache)
    assert by_kind == {"kv_full": 4096 * 4096,
                       "kda_state": 3 * 64 * 128 * 128 * 4,
                       "conv_state": 3 * 3 * 24576 * 2}
    assert counts.layer_kinds(MODEL) == model.kinds


def test_step_bytes_by_hand():
    got = counts.step_bytes(MODEL, 128, 64, 40_000)
    assert got == {
        "weights": counts.step_weight_bytes(MODEL),
        "experts": 128 * 31_457_280,
        "state": 2 * 64 * (12_582_912 + 442_368),
        "cache": 4096 * 40_000}
    assert got["weights"] / 1e9 == pytest.approx(1.38, abs=0.01)


# ----------------------------------------------------------------- readers
@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


def made_up_run(**over):
    run = {"config": CONFIG, "peaks": {"hbm_bytes_per_s": 819e9},
           "reduced": {"modules": {
               "jit__one(7)": {"count": 5.0, "seconds": 0.1},
               "jit__prefill(9)": {"count": 1.0, "seconds": 0.5}}}}
    return dict(run, **over)


def count_steps(reg, moe=True):
    reg.counter("decode_steps_total").inc(10)
    reg.counter("decode_live_positions_total").inc(10 * 40_000)
    reg.counter("generated_tokens_total").inc(10 * 64)
    if moe:
        reg.counter("moe_experts_touched_total").inc(10 * 128)


def test_readers_on_a_made_up_run(registry):
    count_steps(registry)
    parts = counts.step_bytes(MODEL, 128, 64, 40_000)
    roofline = reader("solar_decode_step_roofline").read(made_up_run())
    # a 20 ms step against 7.24 GB / 819 GB/s
    assert roofline == pytest.approx(
        100 * sum(parts.values()) / 819e9 / 0.02)
    assert 40 < roofline < 50
    assert reader("expert_read_share").read(made_up_run()) == \
        pytest.approx(100 * parts["experts"] / sum(parts.values()))
    assert reader("moe_experts_touched_share").read(made_up_run()) == \
        pytest.approx(100 * 128 / 160)


@pytest.mark.parametrize("why,over,moe", [
    ("no trace", {"reduced": None}, True),
    ("no peak table (the CPU rehearsal)", {"peaks": None}, True),
    ("the step is not on the trace", {"reduced": {"modules": {}}}, True),
    ("no routed layer's counter in the program", {}, False)])
def test_roofline_reads_nothing_without_its_input(registry, why, over, moe):
    count_steps(registry, moe)
    assert reader("solar_decode_step_roofline").read(
        made_up_run(**over)) is None, why


def test_counter_readers_need_counters_alone(registry):
    for base in ("expert_read_share", "moe_experts_touched_share"):
        assert reader(base).read(made_up_run()) is None
    count_steps(registry, moe=False)
    for base in ("expert_read_share", "moe_experts_touched_share"):
        assert reader(base).read(made_up_run()) is None
    registry.counter("moe_experts_touched_total").inc(10 * 128)
    for base in ("expert_read_share", "moe_experts_touched_share"):
        assert reader(base).read(made_up_run(reduced=None, peaks=None)) > 0


# ---------------------------------------------------------------- manifest
ENG, DEV, NN = "engines serving/decode.py", "device", "model code nn/ models/"


@pytest.mark.parametrize("base,unit,better,source,layer", [
    ("decode_step_ms", "ms", "lower", "device_trace", ENG),
    ("prefill_time_share", "%", "lower", "device_trace", ENG),
    ("device_idle_share", "%", "lower", "device_trace", DEV),
    ("hbm_peak_gb", "GB", "lower", "program_counter", DEV),
    ("decode_host_ms", "ms", "lower", "program_span", ENG),
    ("decode_loop_stall_ms", "ms", "lower", "program_span", ENG),
    ("engine_lock_wait_ms", "ms", "lower", "program_span", ENG),
    ("prefill_pad_share", "%", "lower", "program_counter", ENG),
    ("decode_runahead_share", "%", "higher", "program_counter", ENG),
    ("cache_write_time_share", "%", "lower", "device_trace", ENG),
    ("solar_decode_step_roofline", "%", "higher", "device_trace", ENG),
    ("expert_read_share", "%", "lower", "program_counter", NN),
    ("moe_experts_touched_share", "%", "higher", "program_counter", NN)])
def test_manifest_entry(base, unit, better, source, layer):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == base + ".moe"]
    assert entry == {"name": base + ".moe", "unit": unit,
                     "better": better, "source": source, "layer": layer,
                     "moves": "serve_tok_s", "workloads": [CELL]}


def test_cell_configuration_and_traffic_are_the_issues():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar_open2_serve", "moe_batch", 1)
    tok_s, = [m for m in MANIFEST["end_to_end"]
              if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"]  # a later cell may be appended
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == "solar_open2_serve"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["serve"]["slots"] == 64 and MODEL["max_len"] == 4096
    assert CONFIG["weights"]["draw"] == "own_init"
    want = {"kind": "closed_loop_own_init", "clients": 64, "ramp_s": 20,
            "trace_slice_s": 8, "max_total_tokens": 4096,
            "prompt_tokens": {"median": 128, "sigma": 0.6, "min": 64,
                              "max": 512},
            "output_tokens": {"median": 512, "sigma": 0.4, "min": 256,
                              "max": 1024}}
    assert {k: TRAFFIC[k] for k in want} == want
    assert TRAFFIC["check"]["prompt_tokens"] == 300
    assert TRAFFIC["check"]["decode_steps"] == 3


def test_top_level_keys_are_the_catalogs_row():
    """Every key of the catalog row's ``config`` at the file's top level:
    as published, but the three in ``reduced``, which state what is held;
    ``published`` is the row itself."""
    catalog = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert CONFIG["published"] == catalog
    held = {"num_hidden_layers": 4, "n_routed_experts": 40,
            "vocab_size": 24576}
    assert {k: CONFIG[k] for k in catalog} == dict(catalog, **held)
    # the router is as wide as published, and the nested group's sizes
    # are the model's arguments
    assert MODEL["num_experts"] == catalog["n_routed_experts"] == \
        CONFIG["num_experts_routed_over"]
    lin = catalog["linear_attn_config"]
    assert (MODEL["kda_heads"], MODEL["kda_head_dim"],
            MODEL["conv_kernel"]) == (lin["num_heads"], lin["head_dim"],
                                      lin["short_conv_kernel_size"])
    assert MODEL["experts_held"] * 8 == MODEL["num_experts"]
    assert MODEL["vocab"] * 8 == catalog["vocab_size"]
    assert MODEL["rms_eps"] == catalog["rms_norm_eps"]
    assert MODEL["routed_scale"] == catalog["routed_scaling_factor"]
