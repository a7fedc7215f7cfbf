"""What the ``glm47_flash_train`` configuration brings: its cell's
rehearsal prints the contract line with its four checks (loss, first
gradient, the warm-up's parameter change, both heads' logits); the cell's
six planted controls (the reference's MTP module fed the unshifted token,
its latent norm dropped, its routed experts weighted by 1.0, its weights
on float8, its gradient of half the batch; the program's step leaving its
parameters unchanged) come out not correct by the harness's own
comparison;
``lib/glm_counts.py`` equals the sizes of the program's own tree at the
published widths (706.5M parameters) and the issue's operation counts; the
four new readers on a hand-written run, and nothing where their input is
missing; the manifest's entries; the configuration's top-level keys
against the catalog's row, its cut and its deployment."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import glm_counts as counts  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "glm47_flash_train.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "steps_4k_glm.json")))
MODEL = CONFIG["model"]
CELL = "train_glm_4k"


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
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    checks, = [json.loads(ln) for ln in lines
               if ln.startswith('{"info": "checks"')]
    return json.loads(lines[-1]), checks


def test_rehearsal_prints_the_contract_line():
    line, checks = rehearse(["run.py"], "--workload", CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # the readers of spans and counters are on the line, no CPU number
    assert {"step_ms.glm", "step_host_ms.glm",
            "moe_busiest_expert_ratio.glm"} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # float32 at toy sizes: both checks to the rounding of f32
    tol = TRAFFIC["rehearsal"]
    assert checks["rel_err"] < tol["loss_rel_tol"] / 10
    assert checks["logits_rel_err"] < tol["logits_rel_tol"] / 10
    assert checks["mtp_logits_rel_err"] < tol["logits_rel_tol"] / 10
    assert checks["grad_rel_err"] < tol["grad_rel_tol"] / 10
    assert checks["step_rel_err"] < tol["step_rel_tol"] / 10
    # in float32 the program chooses every expert as the reference does
    assert checks["flipped_share"] == checks["mtp_flipped_share"] == 0
    assert checks["logits_rel_err_all_rows"] == checks["logits_rel_err"]
    assert checks["losses_finite"]


@pytest.mark.parametrize("control", [
    "mtp_unshifted_reference", "latent_norm_off_reference",
    "routed_scale_one_reference", "fp8_reference", "half_batch_reference",
    "state_unchanged_program"])
def test_control_is_not_correct_by_the_harness_comparison(control):
    line, checks = rehearse(["controls", CELL + ".py"], control,
                            "--trace", "0", seed=2 ** 31 + 11)
    assert line["correct"] is False and line["failed"] == 0
    tol = TRAFFIC["rehearsal"]
    logits = max(checks["logits_rel_err"], checks["mtp_logits_rel_err"])
    if control == "state_unchanged_program":
        # the gradient and the forward are the cell's; the step moved
        # nothing, which reads 1
        assert checks["step_rel_err"] == 1.0
        assert checks["grad_rel_err"] < tol["grad_rel_tol"] / 10
        assert logits < tol["logits_rel_tol"] / 10
        return
    # a wrong gradient moves the step too
    assert checks["grad_rel_err"] > 10 * tol["grad_rel_tol"]
    assert checks["step_rel_err"] > 10 * tol["step_rel_tol"]
    if control == "half_batch_reference":  # the forward is the cell's
        assert logits < tol["logits_rel_tol"] / 10
        return
    assert logits > 10 * tol["logits_rel_tol"]
    if control == "mtp_unshifted_reference":  # the main head is the cell's
        assert checks["logits_rel_err"] < tol["logits_rel_tol"] / 10
        assert checks["flipped_share"] == 0 < checks["mtp_flipped_share"]


# ------------------------------------------------------------------ counts
@pytest.fixture(scope="module")
def tree():
    """Shapes of the program's parameter tree at the published widths:
    ``eval_shape``, nothing is allocated."""
    import jax

    from benchmark.lib.model import build_model
    model = build_model(CONFIG, attn_impl=None)
    return jax.tree_util.tree_leaves_with_path(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def test_parameter_counts_are_the_tree(tree):
    assert counts.params(MODEL) == sum(v.size for _, v in tree) \
        == 706_518_848
    mla = [v.size for path, v in tree
           if "mixer" in [getattr(p, "key", "") for p in path]]
    assert sum(mla) == 6 * counts.mla_params(MODEL)
    experts = sum(v.size for path, v in tree if len(v.shape) == 3)
    assert experts == 5 * 8 * counts.expert_params(MODEL)
    # seeded_params' rule for norm scales finds every norm of the tree
    # (the two latent norms included); the selection biases are the rest
    vectors = [path for path, v in tree if len(v.shape) == 1]
    norms = [p for p in vectors if p[-2].key.startswith("ln")
             and p[-1].key == "weight"]
    assert len(norms) == 6 * 4 + 3 + 1
    assert all(p[-2].key == "router" for p in vectors if p not in norms)


@pytest.mark.parametrize("what,millions", [
    ("mla", 21.76), ("expert", 9.44), ("routed_layer_outside_experts",
                                       31.33),
    ("routed_layer", 106.83), ("dense_layer", 84.68),
    ("vocabulary", 79.30), ("mtp", 115.22)])
def test_sizes_are_the_issues_table(what, millions):
    mla = counts.mla_params(MODEL)
    outside = mla + counts.routed_beside_params(MODEL)
    got = {"mla": mla, "expert": counts.expert_params(MODEL),
           "routed_layer_outside_experts": outside,
           "routed_layer": outside + 8 * counts.expert_params(MODEL),
           "dense_layer": mla + counts.dense_ffn_params(MODEL) + 2 * 2048,
           "vocabulary": 2 * MODEL["vocab"] * MODEL["d_model"],
           "mtp": outside + 8 * counts.expert_params(MODEL)
           + 2 * 2048 * 2048 + 5 * 2048}[what]
    assert got / 1e6 == pytest.approx(millions, abs=0.006)


def test_operations_a_token_are_the_issues():
    """0.957 GFLOP a token forward at 4,096 tokens with the expected
    routed rows (4 picks of 64, 8 held: half a row a token and layer),
    2.87 trained; attention through the latent 54% of it."""
    fwd = counts.forward_flops_per_token(MODEL, 4096, 0.5)
    assert fwd / 1e9 == pytest.approx(0.957, abs=0.0005)
    assert counts.train_flops_per_token(MODEL, 4096, 0.5) == 3 * fwd
    attn = 6 * (2 * counts.mla_params(MODEL, True)
                + counts.attention_flops_per_token(MODEL, 4096))
    assert attn / fwd == pytest.approx(0.54, abs=0.005)


# ----------------------------------------------------------------- readers
TRAINED = {"moe_train_held_picks_total": 10 * 2 * 4096 * 4 * 5 // 8,
           "moe_train_busiest_rows_total": 10 * 5 * 768}


def made_up_run(**over):
    """Ten counted steps of 2 x 4,096 tokens, 20 traced; the flash kernels
    0.8 s and the grouped matmuls 0.2 s of the slice."""
    run = {"config": CONFIG, "batch": 2, "chips": 1, "seq_len": 4096,
           "tok_s_chip": 20_000.0, "traced_steps": 20,
           "train_counters": dict(TRAINED), "counted_steps": 10,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "reduced": {"busy_s": 6.0, "window_s": 6.1, "modules": {},
                       "op_seconds": {"flash_fwd bf16[2,20,4096,256]": 0.5,
                                      "flash_dq x": 0.1, "flash_dkv x": 0.2,
                                      "gmm f32[32768,3072]": 0.12,
                                      "tgmm f32[8,2048,3072]": 0.08,
                                      "fusion x": 4.0}}}
    run.update(over)
    return run


def test_readers_on_a_made_up_run():
    run = made_up_run()
    assert counts.held_rows_per_token(MODEL, TRAINED, 10 * 2 * 4096) == 0.5
    assert counts.rows_per_step(TRAINED, 10) == 2 * 4096 * 5 / 2
    mfu = reader("glm_train_mfu").read(run)
    assert mfu == pytest.approx(
        100 * 3 * 0.9568256e9 * 20_000 / 197e12, rel=1e-6)
    # remat: two forward calls a step
    least = counts.flash_step_flops(MODEL, 2, 4096, 2) / 197e12
    assert reader("mla_flash_roofline").read(run) == pytest.approx(
        100 * least * 20 / 0.8)
    rows = 2 * 4096 * 5 / 2
    least = max(counts.gmm_step_flops(MODEL, rows, 2) / 197e12,
                counts.gmm_step_bytes(MODEL, rows, 2) / 819e9)
    assert reader("moe_gmm_roofline").read(run) == pytest.approx(
        100 * least * 20 / 0.2)
    assert reader("moe_busiest_expert_ratio").read(run) == pytest.approx(
        768 * 8 / (2 * 4096 * 4 / 8))


def test_flash_and_gmm_counts_by_hand():
    fwd = 2 * 4096 * 4096 * 256  # QK^T and PV, causal half, a (row, head)
    assert counts.flash_step_flops(MODEL, 1, 4096, 1) == 6 * 20 * (
        fwd + 5 * 4096 * 4096 * 256)
    assert counts.gmm_step_flops(MODEL, 100, 1) == 3 * 6 * 100 * 2048 * 1536
    # 512 rows a held expert a step and routed layer: near the ridge, so
    # the bytes count
    rows = 5 * 8 * 512
    ratio = (counts.gmm_step_flops(MODEL, rows, 2) / 197e12) / (
        counts.gmm_step_bytes(MODEL, rows, 2) / 819e9)
    assert 1.0 < ratio < 1.3  # compute-bound, by a fifth


@pytest.mark.parametrize("base", [
    "glm_train_mfu", "moe_gmm_roofline", "moe_busiest_expert_ratio"])
def test_counter_readers_read_nothing_without_the_counters(base):
    """The parent has no training counters: nothing, not an error."""
    assert reader(base).read(made_up_run(train_counters={})) is None
    assert reader(base).read(made_up_run(train_counters=None)) is None


@pytest.mark.parametrize("base,over", [
    ("mla_flash_roofline", {"reduced": None}),
    ("mla_flash_roofline", {"peaks": None}),
    ("mla_flash_roofline", {"reduced": {"op_seconds": {"gmm x": 1.0}}}),
    ("moe_gmm_roofline", {"reduced": None}),
    ("moe_gmm_roofline", {"reduced": {"op_seconds": {"flash_fwd x": 1.0}}}),
    ("glm_train_mfu", {"peaks": None})])
def test_a_reader_reads_nothing_without_its_input(base, over):
    assert reader(base).read(made_up_run(**over)) is None


# ---------------------------------------------------------------- manifest
LOOP, DEV, NN = ("training loop optim/optimizer.py", "device",
                 "model code nn/ models/")
KERNELS = "kernels ops/attention_kernel.py"


@pytest.mark.parametrize("base,unit,better,source,layer", [
    ("step_ms", "ms", "lower", "host_clock", LOOP),
    ("step_host_ms", "ms", "lower", "program_span", LOOP),
    ("device_idle_share", "%", "lower", "device_trace", DEV),
    ("hbm_peak_gb", "GB", "lower", "program_counter", DEV),
    ("flash_time_share", "%", "lower", "device_trace", KERNELS),
    ("glm_train_mfu", "%", "higher", "host_clock", NN),
    ("mla_flash_roofline", "%", "higher", "device_trace", KERNELS),
    ("moe_gmm_roofline", "%", "higher", "device_trace", NN),
    ("moe_busiest_expert_ratio", "ratio", "lower", "program_counter", NN)])
def test_manifest_entry(base, unit, better, source, layer):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == base + ".glm"]
    assert entry == {"name": base + ".glm", "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "train_tok_s", "workloads": [CELL]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       base + ".py"))


def test_cell_configuration_and_traffic():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47_flash_train", "steps_4k_glm", 1)
    assert len(cell["why"]) <= 200 and "512 rows a held expert" in \
        cell["why"]
    tok_s, = [m for m in MANIFEST["end_to_end"]
              if m["name"] == "train_tok_s"]
    assert CELL in tok_s["workloads"]
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == "glm47_flash_train"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["reference"] == "glm4_moe_lite"
    assert CONFIG["builder"] == "bigdl_tpu.models.hybrid_moe_lm:HybridMoELM"
    assert CONFIG["train"]["per_chip_batch"] == 2
    assert CONFIG["train"]["compute_dtype"] == "bfloat16"
    assert "mtp_weight" in CONFIG["assumed"] and MODEL["mtp_weight"] == 0.3
    assert "rotary_pairing" in CONFIG["assumed"]
    assert TRAFFIC["kind"] == "train_steps_mtp"
    assert (TRAFFIC["seq_len"], TRAFFIC["pool_batches"],
            TRAFFIC["warmup_steps"], TRAFFIC["trace_steps"]) == (
        4096, 8, 3, 20)
    # 512 rows a held expert a step: 2 x 4,096 tokens x 4 picks / 64
    assert 2 * 4096 * MODEL["top_k"] // MODEL["num_experts"] == 512


def test_top_level_keys_are_the_catalogs_row():
    """Every key of the catalog row's ``config`` at the file's top level,
    as published but the three counts that are cut; ``published`` is the
    row itself; the model's arguments are the row's numbers, no width
    cut; the deployment is stated."""
    catalog = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    held = {"num_hidden_layers": 5, "n_routed_experts": 8,
            "vocab_size": 19360}
    assert CONFIG["published"] == catalog
    assert {k: CONFIG[k] for k in catalog} == dict(catalog, **held)
    assert CONFIG["source"] == ("https://huggingface.co/zai-org/"
                                "GLM-4.7-Flash/blob/main/config.json")
    for arg, key in CONFIG["widths"].items():
        assert MODEL[arg] == held.get(key, catalog[key]), arg
    assert MODEL["num_experts"] == 64 == CONFIG["num_experts_routed_over"]
    assert 19360 * 8 == 154880  # an eighth of the vocabulary
    for words in ("eight", "expert parallelism", "layers 0-4", "MTP",
                  "42 layers"):
        assert words in CONFIG["deployment"]
