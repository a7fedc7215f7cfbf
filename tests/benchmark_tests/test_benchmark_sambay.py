"""What the ``phi4_mini_flash_serve`` configuration brings: its cell's
rehearsal prints the contract line, on weights drawn by the class's own
``init``; the cell's controls (the scan's carry zeroed, the reference on
float8 weights) come out not correct by the harness's own comparison;
``lib/sambay_counts.py`` equals the
sizes of the program's own trees at the published widths (3.85B
parameters, 5,120 bytes a shared-cache row); the two new readers on a
hand-written run, and nothing where their input is missing; the manifest's
entries and the traffic file, letter for letter."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import sambay_counts as counts  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "phi4_mini_flash_serve.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "reason_batch.json")))
MODEL = CONFIG["model"]
CELL = "serve_reason_batch"


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


# toy sizes, bfloat16, own init at 1 / sqrt(width), seeds 2**31 + 5, 11,
# 99, 12345: 0.017 / 0.020 / 0.020 / 0.043; with the carry zeroed 0.076 /
# 0.170 / 0.085 / 0.261 (a prompt of 20 is one chunk of the scan, so only
# the decode steps forget); the reference on float8 weights 0.56
REHEARSAL_TOL = TRAFFIC["rehearsal"]["check"]["rel_tol"]


def test_rehearsal_prints_the_contract_line():
    line, checks = rehearse(["run.py"], "--workload", CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # the counter metrics are on the line, and no CPU number under them
    assert {"prefill_pad_share.reason",
            "decode_cache_read_share.reason"} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # prompt 20 > window 8, padded to the bucket of 32
    assert checks["logits_rel_err"] < REHEARSAL_TOL == 0.06
    assert 32 in checks["prefill_buckets"]


@pytest.mark.parametrize("control", ["carry_zeroed", "fp8_reference"])
def test_control_is_not_correct_by_the_harness_comparison(control):
    """What ``correct`` could not see under N(0, 0.02) on every leaf: the
    same command, one planted change, ``"correct": false`` with every
    request answered."""
    line, checks = rehearse(["controls", CELL + ".py"], control,
                            "--trace", "0", seed=11)
    assert line["correct"] is False and line["failed"] == 0
    assert checks["logits_rel_err"] > 2 * REHEARSAL_TOL
    assert checks["responses_exact"] and checks["greedy_tokens_in_vocab"]


def test_the_draw_is_the_classes_own_with_no_vector_at_rest():
    """``closed_loop_own_init``'s weights: matrices as ``init`` makes them
    (Mamba's conv taps U(+-1/2), poles log(1..N)), vectors moved off 0
    and 1 by N(0, 0.02); the same seed gives the same tree."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import closed_loop_own_init as own
    from benchmark.lib.model import build_model
    cfg = dict(CONFIG, model=dict(MODEL, **CONFIG["rehearsal"]["model"]))
    model = build_model(cfg, attn_impl=None)
    a, b = (own.own_init_params(model, 2 ** 31 + 9, jnp.float32)
            for _ in range(2))
    mamba, ln = a["layers"]["0"]["mixer"], a["layers"]["0"]["ln1"]
    assert np.array_equal(mamba["conv_w"], b["layers"]["0"]["mixer"][
        "conv_w"])
    assert 0.2 < float(jnp.std(mamba["conv_w"])) < 0.35  # 0.5 / sqrt(3)
    np.testing.assert_allclose(np.exp(mamba["a_log"][:, 0]),
                               np.arange(1, 17), rtol=1e-5)
    for vec, at in ((mamba["d"], 1.0), (ln["weight"], 1.0),
                    (ln["bias"], 0.0)):
        assert 0.01 < float(jnp.std(vec - at)) < 0.03
        assert abs(float(jnp.mean(vec)) - at) < 0.01


def test_runner_is_serve_run_with_the_draw_replaced(monkeypatch):
    """The kind's runner: ``lib/serve.py run`` sees a closed loop, finds
    the class's own draw under the name it looks up, and the harness's
    own is back when it returns; a configuration that does not state its
    draw fails by the key's name."""
    from benchmark.lib import closed_loop_own_init as own, serve
    seen, before = {}, serve.seeded_params

    def fake_run(ctx):
        seen.update(kind=ctx["traffic"]["kind"], draw=serve.seeded_params)
        return "out"

    monkeypatch.setattr(serve, "run", fake_run)
    ctx = {"config": CONFIG, "traffic": TRAFFIC}
    assert own.run(ctx) == "out"
    assert seen == {"kind": "closed_loop", "draw": own.own_init_params}
    assert serve.seeded_params is before and ctx["traffic"] is TRAFFIC
    with pytest.raises(KeyError, match="weights"):
        own.run({"config": {"name": "x"}, "traffic": TRAFFIC})
    with pytest.raises(ValueError, match="own_init"):
        own.run({"config": {"weights": {"draw": "seeded"}},
                 "traffic": TRAFFIC})


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
    assert 3.84e9 < counts.params(MODEL) < 3.86e9
    # in a matmul: every matrix but the conv taps and the poles
    mat = sum(v.size for path, v in leaves if len(v.shape) == 2
              and path[-1].key not in ("conv_w", "a_log"))
    assert counts.params(MODEL, matmul_only=True) == mat
    assert counts.step_weight_bytes(MODEL) == 2 * mat


@pytest.mark.parametrize("kind,millions", [
    ("mamba", 41.2), ("window", 19.7), ("full", 19.7), ("gmu", 26.2),
    ("cross", 13.1)])
def test_mixer_sizes_are_the_issues(kind, millions):
    assert counts.mixer_params(MODEL, kind) / 1e6 == pytest.approx(
        millions, abs=0.06)
    n = counts.layer_kinds(MODEL).count(kind)
    assert n == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                 "cross": 7}[kind]


def test_slot_bytes_are_the_cache_tree(trees):
    model, _, cache = trees
    assert counts.cache_row_bytes(MODEL) == 5120
    by_kind = counts.slot_bytes_by_kind(MODEL, MODEL["max_len"])
    assert by_kind == model.cache_bytes_by_kind(cache)
    assert by_kind == {"kv_full": 5120 * 4096, "kv_window": 8 * 5120 * 512,
                       "ssm_state": 9 * 327_680, "conv_state": 9 * 30_720}
    assert counts.layer_kinds(MODEL) == model.kinds


def test_step_bytes_by_hand():
    # 8 readers of the shared cache, 8 rings, state read and written
    assert counts.step_cache_bytes(MODEL, 1000, 300, 2) == (
        5120 * (8 * 1000 + 8 * 300) + 2 * 2 * 9 * (327_680 + 30_720))


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
               "jit__one(7)": {"count": 5.0, "seconds": 0.2},
               "jit__prefill(9)": {"count": 1.0, "seconds": 0.5}}}}
    return dict(run, **over)


def count_steps(reg, window=True):
    reg.counter("decode_steps_total").inc(10)
    reg.counter("decode_live_positions_total").inc(10 * 60_000)
    reg.counter("generated_tokens_total").inc(10 * 64)
    if window:
        reg.counter("decode_window_positions_total").inc(10 * 30_000)


def test_roofline_and_cache_share_of_a_made_up_run(registry):
    count_steps(registry)
    weights = counts.step_weight_bytes(MODEL)
    cache = 5120 * 8 * (60_000 + 30_000) + 2 * 64 * 9 * 358_400
    roofline = reader("sambay_decode_step_roofline").read(made_up_run())
    # a 40 ms step against (6.68 + 4.1 GB) / 819 GB/s
    assert roofline == pytest.approx(
        100 * (weights + cache) / 819e9 / 0.04)
    assert 30 < roofline < 40
    share = reader("decode_cache_read_share").read(made_up_run())
    assert share == pytest.approx(100 * cache / (weights + cache))


@pytest.mark.parametrize("why,over,window", [
    ("no trace", {"reduced": None}, True),
    ("no peak table (the CPU rehearsal)", {"peaks": None}, True),
    ("the step is not on the trace", {"reduced": {"modules": {}}}, True),
    ("no window counter in the program", {}, False)])
def test_roofline_reads_nothing_without_its_input(registry, why, over,
                                                  window):
    count_steps(registry, window)
    assert reader("sambay_decode_step_roofline").read(
        made_up_run(**over)) is None, why


def test_cache_share_needs_counters_alone(registry):
    assert reader("decode_cache_read_share").read(made_up_run()) is None
    count_steps(registry)
    assert reader("decode_cache_read_share").read(
        made_up_run(reduced=None, peaks=None)) > 0


# ---------------------------------------------------------------- manifest
ENG, DEV = "engines serving/decode.py", "device"


@pytest.mark.parametrize("base,unit,better,source,layer", [
    ("decode_step_ms", "ms", "lower", "device_trace", ENG),
    ("prefill_time_share", "%", "lower", "device_trace", ENG),
    ("device_idle_share", "%", "lower", "device_trace", DEV),
    ("hbm_peak_gb", "GB", "lower", "program_counter", DEV),
    ("decode_host_ms", "ms", "lower", "program_span", ENG),
    ("decode_loop_stall_ms", "ms", "lower", "program_span", ENG),
    ("engine_lock_wait_ms", "ms", "lower", "program_span", ENG),
    ("prefill_pad_share", "%", "lower", "program_counter", ENG),
    ("sambay_decode_step_roofline", "%", "higher", "device_trace", ENG),
    ("decode_cache_read_share", "%", "lower", "program_counter", ENG)])
def test_manifest_entry(base, unit, better, source, layer):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == base + ".reason"]
    assert entry == {"name": base + ".reason", "unit": unit,
                     "better": better, "source": source, "layer": layer,
                     "moves": "serve_tok_s", "workloads": [CELL]}


def test_cell_configuration_and_traffic_are_the_issues():
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash_serve", "reason_batch", 1)
    tok_s, = [m for m in MANIFEST["end_to_end"]
              if m["name"] == "serve_tok_s"]
    assert tok_s["workloads"] == ["serve_code_batch", CELL]
    assert CONFIG["reduced"] == [] and CONFIG["serve"]["slots"] == 64
    assert MODEL["max_len"] == 4096
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert CONFIG["published"] == catalog
    # the contract's form: every key of the catalog's entry at the file's
    # top level too, as published (``reduced`` is empty)
    assert {k: CONFIG[k] for k in catalog} == catalog
    want = {"kind": "closed_loop_own_init", "clients": 64, "ramp_s": 20,
            "trace_slice_s": 8, "max_total_tokens": 4096,
            "prompt_tokens": {"median": 256, "sigma": 0.6, "min": 64,
                              "max": 1024},
            "output_tokens": {"median": 768, "sigma": 0.4, "min": 384,
                              "max": 1536}}
    assert {k: TRAFFIC[k] for k in want} == want
    assert TRAFFIC["check"]["prompt_tokens"] == 700
    assert TRAFFIC["check"]["decode_steps"] == 3
