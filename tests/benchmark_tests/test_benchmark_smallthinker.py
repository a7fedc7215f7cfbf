"""What the ``smallthinker_21b_serve`` configuration brings: its cell's
rehearsal prints the contract line with prompts that outrun the toy
window; the cell's four planted controls (the reference without its
window, without its rotation, with the router after attention, on float8
weights) come out not correct by the harness's own comparison, and
``flips`` reads the router's flips; ``lib/smallthinker_counts.py`` equals
the sizes of the program's own trees at the published widths (3.967B
parameters, 117.4 MB a slot); the four new readers on a hand-written run,
and nothing where their input is missing; the manifest's entries, the
configuration's top-level keys against the catalog's row, and the traffic
file, letter for letter."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import smallthinker_counts as counts  # noqa: E402
from benchmark.lib import traffic as tg  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "smallthinker_21b_serve.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "longmix_batch.json")))
MODEL = CONFIG["model"]
CELL = "serve_longmix_batch"


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


# toy sizes, float32 (the configuration's rehearsal says why): the cell
# reads 4e-7 to 6e-7; the least of the controls, the rotation left out,
# 0.0038 to 0.0097 over five seeds (N(0, 0.02) weights at width 64 make
# attention near uniform, so positions move little there)
REHEARSAL_TOL = TRAFFIC["rehearsal"]["check"]["rel_tol"]


def test_rehearsal_prints_the_contract_line():
    line, checks = rehearse(["run.py"], "--workload", CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # the counter metrics are on the line, and no CPU number under them
    assert {"prefill_pad_share.longmix", "window_rows_share.longmix",
            "moe_experts_touched_share.longmix",
            "decode_runahead_share.longmix"} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # prompt 40 past the toy window of 16, padded to the bucket of 64, so
    # the window is live, the rings have wrapped and `last` matters
    assert checks["logits_rel_err"] < REHEARSAL_TOL / 100 == 1e-5
    assert 64 in checks["prefill_buckets"]
    toy = CONFIG["rehearsal"]["model"]
    assert toy["window"] == 16 < TRAFFIC["rehearsal"]["check"][
        "prompt_tokens"] < toy["max_len"]


@pytest.mark.parametrize("control", [
    "window_off_reference", "rope_off_reference",
    "router_after_attention_reference", "fp8_reference"])
def test_control_is_not_correct_by_the_harness_comparison(control):
    line, checks = rehearse(["controls", CELL + ".py"], control,
                            "--trace", "0", seed=2 ** 31 + 11)
    assert line["correct"] is False and line["failed"] == 0
    assert checks["logits_rel_err"] > 2 * REHEARSAL_TOL
    assert checks["responses_exact"] and checks["greedy_tokens_in_vocab"]


def test_flips_control_reads_the_router_on_the_checks_tokens():
    line, checks = rehearse(["controls", CELL + ".py"], "flips",
                            "--trace", "0", seed=2 ** 31 + 99)
    assert line["correct"] is True and line["failed"] == 0
    # 40 tokens x 8 layers; in float32 the program chooses as the
    # reference does
    assert checks["router_pairs"] == 320
    assert checks["router_flipped_pairs"] == 0
    assert checks["logits_rel_err_tail"] < REHEARSAL_TOL / 100
    assert checks["logits_rel_err_tail_routed_as_reference"] \
        == checks["logits_rel_err_tail"]


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
    assert counts.params(MODEL) / 1e9 == pytest.approx(3.967, abs=0.0005)
    # in a matmul, outside the routed experts and the embedding
    mat = sum(v.size for path, v in leaves if len(v.shape) == 2
              and path[0].key != "emb")
    assert counts.step_bytes(MODEL, 0, 0, 0)["weights"] == 2 * mat
    experts = sum(v.size for path, v in leaves if len(v.shape) == 3)
    assert experts == 8 * 64 * counts.expert_params(MODEL)
    # seeded_params' rule for norm scales finds every vector of the tree
    assert all(path[-2].key.startswith("ln") and path[-1].key == "weight"
               for path, v in leaves if len(v.shape) == 1)


@pytest.mark.parametrize("what,millions", [
    ("attention", 20.97), ("expert", 5.898), ("layer", 398.6),
    ("embedding_and_head", 777.9)])
def test_sizes_are_the_issues_table(what, millions):
    got = {"attention": counts.attention_params(MODEL),
           "expert": counts.expert_params(MODEL),
           "layer": counts.layer_matmul_params(MODEL, 64) + 2 * 2560,
           "embedding_and_head": 2 * MODEL["vocab"] * MODEL["d_model"]}[what]
    assert got / 1e6 == pytest.approx(millions, rel=2e-4)


def test_whole_model_is_21b_of_which_3b_a_token():
    """The published sizes from the same functions: 52 layers."""
    pub = CONFIG["published"]
    whole = dict(MODEL, num_layers=pub["num_hidden_layers"],
                 sliding_window_layout=pub["sliding_window_layout"],
                 rope_layout=pub["rope_layout"])
    assert counts.params(whole) / 1e9 == pytest.approx(21.5, abs=0.05)
    active = (counts.params(whole) - MODEL["vocab"] * MODEL["d_model"]
              - 52 * (64 - 6) * counts.expert_params(whole))
    assert active / 1e9 == pytest.approx(3.3, abs=0.05)
    assert counts.layer_kinds(whole).count("global") == 13


def test_slot_bytes_are_the_cache_tree(trees):
    model, _, cache = trees
    assert counts.cache_row_bytes(MODEL) == 2048
    by_kind = counts.slot_bytes_by_kind(MODEL, MODEL["max_len"])
    assert by_kind == model.cache_bytes_by_kind(cache)
    assert by_kind == {"kv_full": 2 * 16384 * 2048,
                       "kv_window": 6 * 4096 * 2048}
    assert sum(by_kind.values()) / 1e6 == pytest.approx(117.4, abs=0.05)
    assert 32 * sum(by_kind.values()) / 1e9 == pytest.approx(3.76, abs=0.005)
    assert counts.layer_kinds(MODEL) == model.kinds


def test_step_bytes_and_operations_by_hand():
    got = counts.step_bytes(MODEL, 490, 96_000, 80_000)
    assert got == {
        "weights": 2 * (151936 * 2560 + 8 * (20_971_520 + 2560 * 64)),
        "experts": 490 * 2 * 5_898_240,
        "cache": 2048 * (2 * 96_000 + 6 * 80_000)}
    # the band: s * w - w^2 / 2 pairs past the window, the triangle before
    assert counts.attention_pairs(MODEL, "window", 8192) == \
        8192 * 4096 - 4096 * 4096 / 2
    assert counts.attention_pairs(MODEL, "window", 4096) == 4096 ** 2 / 2
    assert counts.attention_pairs(MODEL, "global", 8192) == 8192 ** 2 / 2
    assert counts.window_band_flops(MODEL, 4096) == 0
    assert counts.window_band_flops(MODEL, 8192) == \
        6 * 4 * 128 * 28 * (8192 * 4096 - 4096 * 4096 / 2)
    assert counts.window_band_bytes(MODEL, 8192) == \
        6 * 2 * 8192 * (2 * 3584 + 2 * 512)
    # a block of the cell's sixteen prompts: the issue's 46 + 14.7 TFLOP
    block = sum(counts.prefill_flops(MODEL, p)
                for p, _ in tg.length_pairs(TRAFFIC))
    assert block / 1e12 == pytest.approx(60.9, abs=0.3)
    one = counts.prefill_flops(MODEL, 1000)
    assert one == 2.0 * 1000 * 8 * (20_971_520 + 163_840 + 6 * 5_898_240) \
        + 2.0 * 151936 * 2560 + 8 * 4.0 * 3584 * 1000 ** 2 / 2


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


def made_up_run(prompts=(8192, 1000, 13192), **over):
    """A reduced trace by hand: five steps in 0.1 s, the prefills 0.5 s,
    the window kernel 0.05 s, and the engine's prefill spans on a host
    line beside another span."""
    events = [("bigdl:decode_prefill", 0, 10)] + [
        (f"bigdl:prefill_tokens_{s}", i, 5) for i, s in enumerate(prompts)]
    run = {"config": CONFIG, "peaks": PEAKS,
           "planes": [{"name": "/host:CPU", "lines": [
                          {"name": "t", "events": events}]},
                      {"name": "/device:TPU:0", "lines": [
                          {"name": "XLA Ops", "events": [
                              ("bigdl:prefill_tokens_77", 0, 1)]}]}],
           "reduced": {
               "op_seconds": {"flash_fwd_window bf16[28,8192,128]": 0.03,
                              "flash_fwd_window bf16[28,16384,128]": 0.02,
                              "flash_fwd bf16[28,1024,128]": 0.4},
               "modules": {
                   "jit__one(7)": {"count": 5.0, "seconds": 0.1},
                   "jit__prefill(9)": {"count": 2.0, "seconds": 0.3},
                   "jit__prefill(11)": {"count": 1.0, "seconds": 0.2}}}}
    return dict(run, **over)


def count_steps(reg, moe=True, rings=True):
    reg.counter("decode_steps_total").inc(10)
    reg.counter("decode_live_positions_total").inc(10 * 96_000)
    if rings:
        reg.counter("decode_window_positions_total").inc(10 * 80_000)
    if moe:
        reg.counter("moe_experts_touched_total").inc(10 * 490)


def test_readers_on_a_made_up_run(registry):
    count_steps(registry)
    run = made_up_run()
    assert counts.slice_prompt_tokens(run["planes"]) == [8192, 1000, 13192]
    parts = counts.step_bytes(MODEL, 490, 96_000, 80_000)
    roofline = reader("smallthinker_decode_step_roofline").read(run)
    # a 20 ms step against 8.3 GB / 819 GB/s
    assert roofline == pytest.approx(
        100 * sum(parts.values()) / 819e9 / 0.02)
    assert 45 < roofline < 55
    band = sum(counts.window_band_flops(MODEL, s) for s in (8192, 13192))
    assert reader("window_flash_roofline").read(run) == pytest.approx(
        100 * band / 197e12 / 0.05)   # compute-bound; 1,000 adds nothing
    flops = sum(counts.prefill_flops(MODEL, s) for s in (8192, 1000, 13192))
    assert reader("smallthinker_prefill_mfu").read(run) == pytest.approx(
        100 * flops / (197e12 * 0.5))
    assert reader("window_rows_share").read(run) == pytest.approx(
        100 * 6 * 80_000 / (6 * 80_000 + 2 * 96_000))
    assert reader("moe_experts_touched_share").read(run) == pytest.approx(
        100 * 490 / (64 * 8))


@pytest.mark.parametrize("base,why,over", [
    ("smallthinker_decode_step_roofline", "no trace", {"reduced": None}),
    ("smallthinker_decode_step_roofline", "no peak table (the rehearsal)",
     {"peaks": None}),
    ("smallthinker_decode_step_roofline", "the step is not on the trace",
     {"reduced": {"modules": {}, "op_seconds": {}}}),
    ("window_flash_roofline", "no trace", {"reduced": None}),
    ("window_flash_roofline", "no peak table", {"peaks": None}),
    ("window_flash_roofline", "the kernel is not on the trace (the parent)",
     {"reduced": {"modules": {}, "op_seconds": {"flash_fwd x": 1.0}}}),
    ("window_flash_roofline", "no prefill span (the parent)",
     {"planes": []}),
    ("window_flash_roofline", "no prompt past the window in the slice",
     {"planes": [{"name": "/host:CPU", "lines": [{"name": "t", "events": [
         ("bigdl:prefill_tokens_900", 0, 5)]}]}]}),
    ("smallthinker_prefill_mfu", "no trace", {"reduced": None}),
    ("smallthinker_prefill_mfu", "no peak table", {"peaks": None}),
    ("smallthinker_prefill_mfu", "no prefill span (the parent)",
     {"planes": None}),
    ("smallthinker_prefill_mfu", "no prefill on the trace",
     {"reduced": {"modules": {}, "op_seconds": {}}})])
def test_a_reader_reads_nothing_without_its_input(registry, base, why, over):
    count_steps(registry)
    assert reader(base).read(made_up_run(**over)) is None, why


def test_counter_readers_need_their_counters(registry):
    run = made_up_run()
    assert reader("window_rows_share").read(run) is None
    assert reader("smallthinker_decode_step_roofline").read(run) is None
    count_steps(registry, moe=False, rings=False)
    assert reader("window_rows_share").read(run) is None
    assert reader("smallthinker_decode_step_roofline").read(run) is None


# ---------------------------------------------------------------- manifest
ENG, DEV, NN = "engines serving/decode.py", "device", "model code nn/ models/"
KERNELS = "kernels ops/attention_kernel.py"


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
    ("moe_experts_touched_share", "%", "higher", "program_counter", NN),
    ("window_flash_roofline", "%", "higher", "device_trace", KERNELS),
    ("smallthinker_decode_step_roofline", "%", "higher", "device_trace",
     ENG),
    ("smallthinker_prefill_mfu", "%", "higher", "device_trace", ENG),
    ("window_rows_share", "%", "lower", "program_counter", ENG)])
def test_manifest_entry(base, unit, better, source, layer):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == base + ".longmix"]
    assert entry == {"name": base + ".longmix", "unit": unit,
                     "better": better, "source": source, "layer": layer,
                     "moves": "serve_tok_s", "workloads": [CELL]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       base + ".py"))


def test_the_cell_adds_fifteen_metrics_and_nothing_else():
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", ())]
    assert len(mine) == 15 and all(n.endswith(".longmix") for n in mine)
    assert "queue_wait_ms.longmix" not in mine  # 32 callers on 32 slots
    assert MANIFEST["per_layer"][-15:] == [
        m for m in MANIFEST["per_layer"] if m["name"] in mine]
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == "smallthinker_21b_serve"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_cell_configuration_and_traffic_are_the_issues():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker_21b_serve", "longmix_batch", 1)
    assert len(cell["why"]) <= 200 and "an expert a step" in cell["why"]
    tok_s, = [m for m in MANIFEST["end_to_end"]
              if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"]  # a later cell may be appended
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == "smallthinker_21b_serve"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["serve"]["slots"] == 32 == CONFIG["serve"]["max_waiting"]
    assert CONFIG["serve"]["dtype"] == "bfloat16"
    assert CONFIG["reference"] == "smallthinker"
    assert CONFIG["builder"] == "bigdl_tpu.models.hybrid_moe_lm:HybridMoELM"
    want = {"kind": "closed_loop", "clients": 32, "ramp_s": 20,
            "trace_slice_s": 8, "max_total_tokens": 16384,
            "prompt_tokens": {"median": 2048, "sigma": 1.0, "min": 256,
                              "max": 14336},
            "output_tokens": {"median": 256, "sigma": 0.4, "min": 128,
                              "max": 512}}
    assert {k: TRAFFIC[k] for k in want} == want
    assert TRAFFIC["check"]["prompt_tokens"] == 5000
    assert TRAFFIC["check"]["decode_steps"] == 3
    # the issue's sixteen strata, and the longest pair inside a slot
    pairs = tg.length_pairs(TRAFFIC)
    assert [p for p, _ in pairs] == [
        318, 548, 746, 942, 1148, 1370, 1616, 1894, 2215, 2596, 3062, 3655,
        4452, 5623, 7651, 13192]
    assert pairs[-1] == (13192, 349)
    assert sum(p for p, _ in pairs if p > 4096) == 30918


def test_top_level_keys_are_the_catalogs_row():
    """Every key of the catalog row's ``config`` at the file's top level,
    as published but the depth; ``published`` is the row itself; the
    model's arguments are the row's numbers, no width cut."""
    layout = [0, 1, 1, 1] * 13
    catalog = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    assert CONFIG["published"] == catalog
    assert {k: CONFIG[k] for k in catalog} == dict(catalog,
                                                   num_hidden_layers=8)
    assert CONFIG["source"] == ("https://huggingface.co/PowerInfer/"
                                "SmallThinker-21BA3B-Instruct/blob/main/"
                                "config.json")
    assert MODEL == {
        "vocab": 151936, "d_model": 2560, "num_layers": 8, "num_heads": 28,
        "num_kv_heads": 4, "head_dim": 128,
        "sliding_window_layout": layout[:8], "rope_layout": layout[:8],
        "window": 4096, "rope_theta": 1500000, "num_experts": 64,
        "experts_held": 64, "share": 0, "top_k": 6, "expert_width": 768,
        "shared_experts": 0, "router_score": "softmax_topk",
        "expert_act": "relu", "router_input": "layer_input",
        "rms_eps": 1e-06, "max_len": 16384}
    for arg, key in CONFIG["widths"].items():
        assert MODEL[arg] == (8 if key == "num_hidden_layers"
                              else catalog[key]), arg
    assert "layout" not in " ".join(CONFIG["widths"])  # counts, not widths
    assert set(CONFIG["assumed"]) >= {
        "router_input", "no_bias", "no_qk_norm", "rope_pairing",
        "window_edge", "initializer_range", "secondary_experts"}
