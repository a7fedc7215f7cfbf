"""HBM attribution (ISSUE 12): static plan vs the compiler's own
memory_analysis, fit forecasting, live-sampler degradation on CPU, the
OOM post-mortem path, KV-cache byte gauges, and the lint rule.

The load-bearing contracts:

* the per-category plan TOTALS to ``compiled.memory_analysis()``'s
  number by construction (drift is a visible row, not a mismatch);
* the perf JSON schema is stable — the memory columns are null obs-off
  and filled (source: plan on CPU) under --obs;
* a simulated RESOURCE_EXHAUSTED leaves a parseable MemoryReport in the
  installed trace dir and a fault-log stamp, and the crash still
  propagates;
* ``run_memory_rules`` errors above HBM, warns above 85%, stays silent
  with room.
"""

import json

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs import memory
from bigdl_tpu.obs.metrics import MetricsRegistry
from bigdl_tpu.obs.spans import Tracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Fresh tracing/registry/OOM-context per test (process is shared
    across test modules)."""
    obs.disable()
    obs.reset_registry()
    memory._reset_context()
    yield
    obs.disable()
    obs.reset_registry()
    memory._reset_context()


@pytest.fixture(scope="module")
def lenet_plans():
    """Compiled-step plans for lenet5 at three batches (one compile
    each; module-scoped so the suite pays it once)."""
    return {b: memory.plan_for_model("lenet5", b) for b in (16, 32, 64)}


# ------------------------------------------------------------- byte math
def test_tree_bytes_concrete_and_abstract():
    import jax

    conc = {"a": np.zeros((4, 8), np.float32),
            "b": [np.zeros(3, np.int32)]}
    assert memory.tree_bytes(conc) == 4 * 8 * 4 + 3 * 4
    abst = {"a": jax.ShapeDtypeStruct((4, 8), np.float32),
            "b": [jax.ShapeDtypeStruct((3,), np.int32)]}
    assert memory.tree_bytes(abst) == memory.tree_bytes(conc)
    assert memory.tree_bytes(None) == 0


def test_device_hbm_matching():
    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert memory.device_hbm_bytes(Dev("TPU v4")) == (32e9, "v4")
    assert memory.device_hbm_bytes(Dev("TPU v5 lite")) == (16e9, "v5lite")
    assert memory.device_hbm_bytes(Dev("cpu")) == (8e9, "cpu")
    with pytest.raises(ValueError, match="QuantumChip 9000"):
        memory.device_hbm_bytes(Dev("QuantumChip 9000"))


# ------------------------------------------------- plan vs the compiler
def test_plan_totals_to_memory_analysis(lenet_plans):
    plan = lenet_plans[16]
    ct = plan["compiler_total_bytes"]
    assert ct is not None and ct > 0
    # totals BY CONSTRUCTION: the category table == the compiler number
    assert sum(plan["categories"].values()) == plan["total_bytes"]
    assert abs(plan["total_bytes"] - ct) <= 0.05 * ct
    # the known pytrees actually landed in their rows
    assert plan["categories"]["params"] > 0
    assert plan["categories"]["optimizer"] > 0  # SGD momentum slots
    assert plan["categories"]["activations"] > 0
    assert plan["categories"]["input"] > 0
    assert plan["batch"] == 16 and plan["model"] == "lenet5"
    assert plan["headroom_bytes"] > 0  # lenet5 fits the 8 GB CPU nominal


def test_plan_abstract_only_no_compile():
    import jax

    params = {"w": jax.ShapeDtypeStruct((128, 128), np.float32)}
    plan = memory.build_plan(params=params, opt_state=params,
                             batch=jax.ShapeDtypeStruct((8, 128),
                                                        np.float32),
                             batch_size=8)
    pb = 128 * 128 * 4
    assert plan["categories"]["params"] == pb
    assert plan["categories"]["gradients"] == pb  # params-sized estimate
    assert plan["compiler"] is None
    assert plan["total_bytes"] == sum(plan["categories"].values())


def test_render_and_compact(lenet_plans):
    plan = lenet_plans[16]
    text = memory.render(plan, memory.forecast(lenet_plans[16],
                                               lenet_plans[32]))
    assert "params" in text and "TOTAL" in text
    assert "compiler total" in text and "headroom" in text
    assert "predicted max batch" in text
    c = memory.compact(plan)
    json.dumps(c)  # JSON-stampable
    assert c["total_bytes"] == plan["total_bytes"]
    assert "outputs" not in c["categories"] or \
        c["categories"].get("outputs", 1) > 0  # zero rows dropped


# ------------------------------------------------------------ forecaster
def test_forecast_monotone_and_predictive(lenet_plans):
    p16, p32, p64 = (lenet_plans[b] for b in (16, 32, 64))
    assert p32["total_bytes"] > p16["total_bytes"]  # per-sample cost real
    assert p64["total_bytes"] > p32["total_bytes"]
    fc = memory.forecast(p16, p32)
    assert fc["bytes_per_sample"] > 0
    assert fc["fit_batches"] == [16, 32]
    # the fit passes through its two points exactly
    assert fc["fixed_bytes"] + 16 * fc["bytes_per_sample"] == \
        pytest.approx(p16["total_bytes"], abs=64)
    # and extrapolates: b=64 actual within 10% of the linear prediction
    pred64 = fc["fixed_bytes"] + 64 * fc["bytes_per_sample"]
    assert abs(pred64 - p64["total_bytes"]) <= 0.10 * p64["total_bytes"]
    # max batch: monotone consequence of headroom >> plan
    assert fc["predicted_max_batch"] > 64
    # argument-order insensitivity
    assert memory.forecast(p32, p16) == fc
    with pytest.raises(ValueError):
        memory.forecast(p16, p16)


# ----------------------------------------------------- perf JSON columns
def _perf_run(tmp_path, obs_on):
    from bigdl_tpu.cli import common
    from bigdl_tpu.cli.perf import run

    obs_state = None
    if obs_on:
        obs.enable()
        obs_state = common.ObsState(True, str(tmp_path / "tr"), None,
                                    None)
    return run("lenet5", 16, 4, "constant", use_bf16=False,
               obs_state=obs_state)


def test_perf_mem_columns_null_obs_off(tmp_path):
    out = _perf_run(tmp_path, obs_on=False)
    for k in ("hbm_peak_bytes", "hbm_headroom_frac", "mem"):
        assert k in out and out[k] is None


def test_perf_mem_columns_filled_under_obs(tmp_path):
    out = _perf_run(tmp_path, obs_on=True)
    assert out["hbm_peak_bytes"] and out["hbm_peak_bytes"] > 0
    assert 0.0 < out["hbm_headroom_frac"] <= 1.0
    m = out["mem"]
    assert m["source"] == "plan"  # CPU has no live memory_stats
    assert m["total_bytes"] == out["hbm_peak_bytes"]
    assert m["categories"]["params"] > 0
    assert m["compiler_total_bytes"] == m["total_bytes"]
    json.dumps(out)  # the whole line still serializes


# --------------------------------------------------------- live sampler
def test_sampler_degrades_on_cpu():
    s = memory.HbmSampler()
    assert s.sample(step=0) is None  # CPU: memory_stats() is None
    assert s.peak_bytes is None and s.annotation() is None


def test_sampler_with_fake_device_stats():
    class Dev:
        device_kind = "TPU v4"

        def __init__(self):
            self.stats = {"bytes_in_use": 100, "peak_bytes_in_use": 150,
                          "largest_free_block_bytes": 50}

        def memory_stats(self):
            return self.stats

    reg = MetricsRegistry()
    dev = Dev()
    s = memory.HbmSampler(device=dev, registry=reg)
    got = s.sample(step=1)
    assert got["bytes_in_use"] == 100
    assert s.peak_bytes == 150
    dev.stats = dict(dev.stats, bytes_in_use=200, peak_bytes_in_use=300)
    s.sample(step=2)
    assert s.peak_bytes == 300
    assert len(s.history) == 2
    text = reg.render()
    assert "hbm_bytes_in_use 200" in text
    assert "hbm_peak_bytes 300" in text
    ann = s.annotation()
    assert ann["peak_bytes"] == 300 and ann["samples"] == 2


# ------------------------------------------------------ OOM post-mortem
def test_is_resource_exhausted():
    assert memory.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating"))
    assert memory.is_resource_exhausted(RuntimeError("Out of memory"))
    assert not memory.is_resource_exhausted(ValueError("shape mismatch"))


def test_handle_oom_writes_report_and_fault_log(tmp_path, monkeypatch):
    log = tmp_path / "faults.jsonl"
    monkeypatch.setenv("BIGDL_FAULT_LOG", str(log))
    plan = {"total_bytes": 123, "hbm_bytes": 100, "categories": {}}
    memory.install(trace_dir=str(tmp_path / "tr"), plan=plan)
    exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory 9.5G")
    path = memory.handle_oom(exc, "test_site")
    assert path is not None
    report = json.load(open(path))
    assert report["event"] == "oom"
    assert report["context"] == "test_site"
    assert report["plan"]["total_bytes"] == 123
    assert "RESOURCE_EXHAUSTED" in report["error"]
    assert isinstance(report["top_live_buffers"], list)
    stamp = json.loads(log.read_text().strip().splitlines()[-1])
    assert stamp["event"] == "oom" and stamp["report"] == path


def test_handle_oom_ignores_non_oom_and_never_raises(tmp_path):
    memory.install(trace_dir=str(tmp_path / "tr"))
    assert memory.handle_oom(ValueError("not an oom"), "x") is None
    assert not (tmp_path / "tr").exists()
    # armed with a plan that explodes on json.dump: still returns, the
    # crash path is never made worse by the autopsy
    memory.install(plan={"bad": object()})
    assert memory.handle_oom(RuntimeError("RESOURCE_EXHAUSTED"),
                             "x") is None


def test_oom_catch_site_serving_predict(tmp_path):
    """The engine's RESOURCE_EXHAUSTED catch writes the report, then the
    exception still propagates to the caller."""
    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceEngine

    m = nn.Sequential(nn.Linear(12, 16), nn.ReLU(), nn.Linear(16, 7),
                      nn.LogSoftMax())
    params = m.init(__import__("jax").random.PRNGKey(0))
    eng = InferenceEngine(m, params, buckets=(8,))
    memory.install(trace_dir=str(tmp_path))

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory")

    x = np.zeros((4, 12), np.float32)
    eng.predict_scores(x)  # populate the compiled cache
    for key in list(eng._compiled):
        eng._compiled[key] = boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.predict_scores(x)
    report = json.load(open(tmp_path / memory.OOM_REPORT_NAME))
    assert report["context"] == "serving_predict"


# --------------------------------------------------- KV gauges + serving
def test_kv_cache_gauges_known_config():
    import jax

    from bigdl_tpu import models
    from bigdl_tpu.serving import DecodeEngine

    slots, max_len = 2, 64
    model = models.transformer_lm(50, d_model=32, num_layers=2,
                                  num_heads=2, max_len=max_len)
    params = model.init(jax.random.PRNGKey(1))
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=slots, max_len=max_len,
                      metrics=reg)
    expect = memory.tree_bytes(de._cache)
    # layers x {k,v} x slots x heads x max_len x head_dim x itemsize
    assert expect == 2 * 2 * slots * 2 * max_len * (32 // 2) * 4
    text = reg.render()
    assert f"kv_cache_bytes {expect}" in text
    assert f"kv_cache_bytes_per_slot {expect // slots}" in text


def test_engine_provenance_bucket_hbm():
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceEngine

    m = nn.Sequential(nn.Linear(12, 16), nn.ReLU(), nn.Linear(16, 7),
                      nn.LogSoftMax())
    eng = InferenceEngine(m, m.init(jax.random.PRNGKey(0)), buckets=(8,))
    eng.predict_scores(np.zeros((4, 12), np.float32))
    prov = eng.provenance()
    assert prov.get("bucket_8_hbm_bytes", 0) > 0


# ------------------------------------------------------------- lint rule
def _fake_plan(total, hbm=8_000_000_000):
    return {"total_bytes": total, "hbm_bytes": hbm, "batch": 64,
            "model": "fake", "device": "cpu",
            "categories": {"params": total // 2,
                           "activations": total - total // 2}}


def test_memory_rules_fire_and_silence():
    from bigdl_tpu.analysis import run_memory_rules
    from bigdl_tpu.analysis.rules import HBM_WARN_FRAC

    over = run_memory_rules(_fake_plan(10_000_000_000)).findings
    assert [f.rule for f in over] == ["hbm-oversubscribed"]
    assert over[0].severity == "error"
    tight = run_memory_rules(
        _fake_plan(int(8_000_000_000 * (HBM_WARN_FRAC + 0.05)))).findings
    assert [f.rule for f in tight] == ["hbm-tight"]
    assert tight[0].severity == "warning"
    assert run_memory_rules(_fake_plan(1_000_000_000)).findings == []
    assert run_memory_rules(None).findings == []


def test_lint_perf_model_carries_memory_pass():
    from bigdl_tpu.analysis import lint_perf_model

    rep = lint_perf_model("lenet5", batch=16, trace=False)
    # lenet5 fits the CPU nominal with room: no memory finding, and no
    # lint-trace-error from the memory pass either
    assert all(f.rule not in ("hbm-oversubscribed", "hbm-tight")
               for f in rep.findings)
    assert all("memory rules skipped" not in f.message
               for f in rep.findings)


# ------------------------------------------------- span instant/counter
def test_instant_and_counter_chrome_export():
    clk_t = [10.0]
    tr = Tracer(clock=lambda: clk_t[0])
    obs.set_tracer(tr)
    with obs.span("step"):
        clk_t[0] += 1.0
        obs.instant("fault:device_loss", site="dispatch")
        obs.counter("hbm", {"bytes_in_use": 42})
        clk_t[0] += 1.0
    trace = json.loads(json.dumps(tr.chrome_trace()))  # JSON-clean
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    inst = by_name["fault:device_loss"]
    assert inst["ph"] == "i" and inst["s"] == "g" and "dur" not in inst
    assert inst["args"]["site"] == "dispatch"
    ctr = by_name["hbm"]
    assert ctr["ph"] == "C" and ctr["args"] == {"bytes_in_use": 42}
    step = by_name["step"]
    assert step["ph"] == "X" and step["dur"] == pytest.approx(2e6)
    # markers sit inside the enclosing span on the timeline
    assert step["ts"] <= inst["ts"] <= step["ts"] + step["dur"]


def test_instant_noop_when_disabled():
    assert not obs.enabled()
    obs.instant("x", a=1)  # must not raise, must not allocate events
    obs.counter("y", {"v": 1})
