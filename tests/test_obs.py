"""Unified observability layer (ISSUE 7): span tracing, shared
registry, capture windows, CLI wiring.

Covers the satellite contract: span nesting + thread-safety under an
injected clock, Chrome-trace JSON validity (loads, events properly
nested, pid/tid/ts sane), registry exposition from the training path,
a capture-window trigger producing a parseable xplane on CPU, and
disabled-mode overhead (span() with no tracer and no profiler session
reads no clock and appends nothing; obs-off perf output identical modulo
the new null columns). ISSUE 26: every span is also a profiler annotation
``bigdl:<name>`` on the device trace's clock, the decode loop, the
streamed front and the loss fetch carry the spans the per-layer metrics
read, and tracing on adds no device sync to the Optimizer.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import obs
from bigdl_tpu.obs.spans import Tracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and a fresh global
    registry (other test modules share the process)."""
    obs.disable()
    obs.reset_registry()
    yield
    obs.disable()
    obs.reset_registry()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


# ------------------------------------------------------------------ spans
def test_span_nesting_under_injected_clock():
    clk = FakeClock(10.0)
    tr = Tracer(clock=clk)
    obs.set_tracer(tr)
    with obs.span("outer"):
        clk.tick(1.0)
        with obs.span("inner", step=3):
            clk.tick(0.25)
        clk.tick(0.5)
    evs = tr.events()
    # completed-on-exit ordering: inner closes first
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert inner["ts"] == pytest.approx(11.0)
    assert inner["dur"] == pytest.approx(0.25)
    assert inner["depth"] == 1 and inner["args"] == {"step": 3}
    assert outer["ts"] == pytest.approx(10.0)
    assert outer["dur"] == pytest.approx(1.75)
    assert outer["depth"] == 0
    # nesting containment on the fake timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_span_without_tracer_or_session_reads_no_clock():
    """No ``--obs`` tracer and no profiler session: the span is the bare
    profiler annotation; no clock is read and nothing is appended."""
    import jax

    reads = []
    tr = Tracer(clock=lambda: reads.append(1) or 0.0)
    obs.set_tracer(tr)
    with obs.span("seen"):
        pass
    assert len(reads) == 2 and len(tr.events()) == 1
    obs.disable()
    assert not obs.enabled()
    for i in range(100):
        s = obs.span("a", x=i)
        assert type(s) is jax.profiler.TraceAnnotation
        with s:
            pass
    assert len(reads) == 2 and len(tr.events()) == 1


def _bigdl_spans_by_line(planes):
    """``[[(name, start_ns, end_ns), ...], ...]``: the program's spans of
    every host thread line that has any."""
    from benchmark.lib.spans import TAG, host_lines

    lines = [[(n[len(TAG):], s, s + d) for n, s, d in events
              if n.startswith(TAG)] for events in host_lines(planes)]
    return [ln for ln in lines if ln]


def test_span_is_a_profiler_annotation_nested_by_thread(tmp_path):
    """Under a ``jax.profiler`` session a span lands in a host plane of
    the session's own trace as ``bigdl:<name>`` (keyword arguments kept
    out of the name), children inside their parents on the line of the
    thread that ran them; the ``--obs`` ring sees the same spans."""
    from benchmark.lib import trace

    tr = obs.enable()
    straddles = obs.span("straddles_the_start")
    straddles.__enter__()
    trace.start(str(tmp_path))
    straddles.__exit__(None, None, None)

    both = threading.Barrier(2)  # alive at once: two thread ids

    def work(i):
        both.wait(10)
        with obs.span("outer", worker=i):
            with obs.span("inner", rid=f"r{i}"):
                pass
        both.wait(10)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    lines = _bigdl_spans_by_line(trace.stop_and_load(str(tmp_path)))
    assert len(lines) == 2  # one line a thread, whatever the lines' names
    for line in lines:
        (outer,) = [e for e in line if e[0] == "outer"]
        (inner,) = [e for e in line if e[0] == "inner"]
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
    # a span open when the session began is not in it; the ring has it
    assert sorted(e["name"] for e in tr.events()) == [
        "inner", "inner", "outer", "outer", "straddles_the_start"]


ENGINE_SPANS = {"decode_idle", "decode_lock_wait", "decode_round",
                "decode_args", "decode_step", "decode_host_read",
                "decode_emit", "decode_prefill", "submit_lock_wait"}
FRONT_SPANS = {"generate_request", "generate_admit",
               "generate_first_token_wait", "generate_stream"}
LOOP_SPANS = {"train_step", "loss_fetch", "data_wait", "h2d", "dispatch"}


def test_engine_front_and_loop_spans_in_a_profiler_session(traced_toy_run):
    """The real serving stack and training loop at toy sizes under one
    CPU profiler session: every span of ISSUE 26 is there, nested as the
    per-layer metrics assume, and the counters count what they say."""
    lines = _bigdl_spans_by_line(traced_toy_run["planes"])
    names = {e[0] for line in lines for e in line}
    assert ENGINE_SPANS | FRONT_SPANS | LOOP_SPANS <= names

    def inside(line, child, parent):
        kids = [e for e in line if e[0] == child]
        parents = [e for e in line if e[0] == parent]
        return kids and all(any(p[1] <= k[1] and k[2] <= p[2]
                                for p in parents) for k in kids)

    (loop,) = [ln for ln in lines if any(e[0] == "decode_round"
                                         for e in ln)]
    for child, parent in (("decode_host_read", "decode_step"),
                          ("decode_args", "decode_round"),
                          ("decode_step", "decode_round"),
                          ("decode_emit", "decode_round")):
        assert inside(loop, child, parent), (child, parent)
    # a hand-off's prefill runs on the decode thread inside the emit loop
    assert inside(loop, "decode_prefill", "decode_emit")
    # a handler thread a request (a later thread may reuse an ended
    # thread's id, and with it its line)
    handlers = [ln for ln in lines if any(e[0] == "generate_request"
                                          for e in ln)]
    assert sum(e[0] == "generate_request" for ln in handlers
               for e in ln) == 5
    for line in handlers:
        for child in ("generate_admit", "generate_first_token_wait",
                      "generate_stream"):
            assert inside(line, child, "generate_request"), child
        assert inside(line, "submit_lock_wait", "generate_admit")
    (train,) = [ln for ln in lines if any(e[0] == "train_step"
                                          for e in ln)]
    assert inside(train, "loss_fetch", "train_step")

    reg, before = traced_toy_run["registry"], traced_toy_run["before"]
    moved = {k: reg.counter(k).value - v for k, v in before.items()}
    assert moved["prefill_bucket_tokens_total"] == sum(
        traced_toy_run["buckets"])
    assert moved["prompt_tokens_total"] == sum(traced_toy_run["prompts"])
    # four requests on two slots: two sat in the queue
    assert moved["decode_queued_total"] == 2
    assert moved["decode_queue_wait_seconds_total"] > 0
    assert "prefill_bucket_tokens_total" in reg.render()


def test_optimizer_syncs_the_same_with_tracing_on_and_off(monkeypatch):
    """ISSUE 26: tracing on adds no device sync. Per step the Optimizer
    makes the same ``block_until_ready`` calls (none) and loss fetches
    (one a log point) under ``--obs`` as without it."""
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import SGD, Optimizer, Trigger, optimizer

    calls = {"block_until_ready": 0, "fetch": 0}
    real_block = jax.block_until_ready

    def counting_block(x):
        calls["block_until_ready"] += 1
        return real_block(x)

    def counting_float(x):
        calls["fetch"] += isinstance(x, jax.Array)
        return float(x)

    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    # the loop's float(loss) is the fetch: shadow the builtin in its module
    monkeypatch.setattr(optimizer, "float", counting_float, raising=False)
    x = np.random.RandomState(0).randn(32, 6).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)

    def run(log_every):
        calls.update(block_until_ready=0, fetch=0)
        Optimizer(nn.Sequential(nn.Linear(6, 2), nn.LogSoftMax()),
                  BatchDataSet(x, y, 8), nn.ClassNLLCriterion(),
                  optim_method=SGD(learning_rate=0.1),
                  end_when=Trigger.max_iteration(6),
                  log_every=log_every).optimize()
        return dict(calls)

    for log_every in (1, 3):
        obs.disable()
        off = run(log_every)
        tr = obs.enable()
        on = run(log_every)
        assert on == off == {"block_until_ready": 0,
                             "fetch": 6 // log_every}
        names = [e["name"] for e in tr.events()]
        assert names.count("train_step") >= 6
        assert names.count("loss_fetch") == 6 // log_every
        assert "device" not in names
        # the device phase is fed by the wait inside the loss fetch
        page = obs.get_registry().render()
        assert f"train_phase_device_ms_count {6 // log_every}" in page
        obs.reset_registry()


def test_span_thread_safety_and_tids():
    tr = obs.enable(capacity=4096)
    n_threads, n_spans = 4, 200
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for i in range(n_spans):
            with obs.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * n_spans  # nothing lost or corrupted
    tids = {e["tid"] for e in evs}
    assert len(tids) == n_threads  # stable small per-thread ids
    per_tid = {tid: sorted(e["ts"] for e in evs if e["tid"] == tid)
               for tid in tids}
    for tid, n in ((t, len(v)) for t, v in per_tid.items()):
        assert n == n_spans


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = obs.enable(capacity=8)
    for i in range(20):
        with obs.span(f"s{i}"):
            pass
    assert len(tr.events()) == 8
    assert tr.dropped == 12
    # oldest dropped, newest kept
    assert tr.events()[-1]["name"] == "s19"


def test_chrome_trace_export_valid_and_nested(tmp_path):
    clk = FakeClock(5.0)
    tr = Tracer(clock=clk)
    obs.set_tracer(tr)
    for step in range(3):
        with obs.span("step", i=step):
            clk.tick(0.001)
            with obs.span("h2d"):
                clk.tick(0.002)
            with obs.span("device"):
                clk.tick(0.004)
            clk.tick(0.001)
    path = str(tmp_path / "trace.json")
    n = tr.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)  # must json-load
    evs = doc["traceEvents"]
    assert n == len(evs) == 9
    assert all(e["ph"] == "X" for e in evs)
    assert len({e["pid"] for e in evs}) == 1
    # ts monotone non-decreasing per tid in export order
    for tid in {e["tid"] for e in evs}:
        ts = [e["ts"] for e in evs if e["tid"] == tid]
        assert ts == sorted(ts)
    # every h2d/device interval sits inside a step interval
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in evs
             if e["name"] == "step"]
    for e in evs:
        if e["name"] in ("h2d", "device"):
            lo, hi = e["ts"], e["ts"] + e["dur"]
            assert any(s <= lo and hi <= t + 1e-6 for s, t in steps)


# --------------------------------------------------------------- registry
def test_global_registry_singleton_and_reset():
    r1 = obs.get_registry()
    assert obs.get_registry() is r1
    assert r1.namespace == "bigdl"
    obs.reset_registry()
    assert obs.get_registry() is not r1


def test_phase_histograms_idempotent():
    reg = obs.get_registry()
    h1 = obs.phase_histograms(reg, "train")
    h2 = obs.phase_histograms(reg, "train")
    assert set(h1) == set(obs.TRAIN_PHASES)
    for ph in h1:
        assert h1[ph] is h2[ph]  # registry dedups by name


def _train_tiny(epochs=1):
    import jax.numpy as jnp  # noqa: F401 (backend init)

    from bigdl_tpu import nn
    from bigdl_tpu.core import Sequential
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    rs = np.random.RandomState(0)
    x = rs.randn(32, 8).astype(np.float32)
    y = rs.randint(0, 3, 32)
    ds = BatchDataSet(x, y, batch_size=8)
    model = Sequential(nn.Linear(8, 3), nn.LogSoftMax())
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(),
                    optim_method=SGD(learning_rate=0.1),
                    end_when=Trigger.max_epoch(epochs))
    opt.optimize()
    return opt


def test_training_publishes_phases_to_registry():
    obs.enable()
    opt = _train_tiny()
    totals = opt.phase_totals()
    # dispatch covers the jitted step calls; device is the wait inside
    # the loss fetch of each log point
    assert totals["dispatch"] > 0
    assert totals["device"] > 0
    page = obs.get_registry().render()
    assert "bigdl_train_phase_dispatch_ms_count" in page
    assert "bigdl_train_phase_dispatch_seconds_total" in page
    assert "bigdl_train_phase_data_wait_seconds_total" in page
    # histogram saw one observation per dispatch (4 batches x 1 epoch)
    h = obs.get_registry().histogram("train_phase_dispatch_ms")
    assert h.count == 4


def test_training_obs_off_still_meters_feed_stall():
    """Satellite #1: fetch/dispatch seconds surface in EVERY run — the
    old fetch_accum was measured then dropped."""
    assert not obs.enabled()
    opt = _train_tiny()
    totals = opt.phase_totals()
    assert totals["dispatch"] > 0
    assert totals["data_wait"] >= 0
    # the loop fetches the loss with tracing off too: the same phase
    assert totals["device"] > 0
    page = obs.get_registry().render()
    assert "bigdl_train_phase_dispatch_seconds_total" in page
    # but no per-step histograms were fed (no per-step locking obs-off)
    assert "train_phase_dispatch_ms_count" not in page


def test_metrics_http_listener_scrapes_registry():
    reg = obs.get_registry()
    reg.counter("smoke_total", "x").inc(3)
    srv = obs.start_metrics_server(reg, port=0)
    try:
        with urllib.request.urlopen(srv.url, timeout=10) as r:
            page = r.read().decode()
        assert "bigdl_smoke_total 3" in page
        health = srv.url.replace("/metrics", "/healthz")
        with urllib.request.urlopen(health, timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.close()


def test_serving_shim_reexports():
    """Satellite #2: serving/metrics.py keeps its surface (same classes,
    same default namespace) while the implementation lives in obs."""
    from bigdl_tpu.obs import metrics as obs_metrics
    from bigdl_tpu.serving import metrics as serving_metrics

    assert serving_metrics.MetricsRegistry is obs_metrics.MetricsRegistry
    assert serving_metrics.Histogram is obs_metrics.Histogram
    reg = serving_metrics.MetricsRegistry()
    assert reg.namespace == "bigdl_serving"  # pinned default


# ---------------------------------------------------------------- capture
def test_parse_trace_steps():
    from bigdl_tpu.obs.capture import parse_trace_steps
    assert parse_trace_steps("5@20") == (5, 20)
    assert parse_trace_steps("1@0") == (1, 0)
    for bad in ("", "5", "@3", "0@2", "a@b", "3@"):
        with pytest.raises(ValueError):
            parse_trace_steps(bad)


def test_capture_window_produces_parseable_xplane(tmp_path):
    """--traceSteps N@M on CPU: the window opens at M, closes at M+N,
    and the resulting xplane parses with utils/xplane (the PR 3
    reader)."""
    import jax
    import jax.numpy as jnp

    ctl = obs.CaptureController(str(tmp_path / "tr"), trace_steps="2@1",
                                install_signal=False)
    f = jax.jit(lambda a: a * 2 + 1)
    for step in range(5):
        ctl.on_step(step)
        f(jnp.arange(8.0)).block_until_ready()
    ctl.finish()
    assert len(ctl.captures) == 1
    cap = ctl.captures[0]
    assert cap["start_step"] == 1 and cap["stop_step"] == 3
    assert cap["ok"], cap.get("error")
    assert cap["planes"] >= 1
    from bigdl_tpu.utils.xplane import parse_xspace
    assert len(parse_xspace(cap["xplane"])) == cap["planes"]


def test_capture_touch_file_trigger(tmp_path):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "tr")
    ctl = obs.CaptureController(d, window_steps=2, install_signal=False)
    f = jax.jit(lambda a: a + 1)
    f(jnp.arange(4.0)).block_until_ready()  # compile outside windows
    for step in range(8):
        if step == 3:
            open(ctl.touch_file, "w").close()
        ctl.on_step(step)
        f(jnp.arange(4.0)).block_until_ready()
    ctl.finish()
    assert len(ctl.captures) == 1
    cap = ctl.captures[0]
    assert cap["trigger"] == "touch"
    assert cap["start_step"] == 3 and cap["stop_step"] == 5
    assert cap["ok"], cap.get("error")
    # the touch file was consumed: one touch = one capture
    import os
    assert not os.path.exists(ctl.touch_file)


# ------------------------------------------------------------- CLI wiring
def _perf_run(tmp_path, obs_on):
    from bigdl_tpu.cli import common
    from bigdl_tpu.cli.perf import run

    obs_state = None
    if obs_on:
        obs.enable()
        obs_state = common.ObsState(True, str(tmp_path / "tr"), None,
                                    None)
    return run("lenet5", 16, 6, "constant", use_bf16=False,
               obs_state=obs_state)


def test_perf_phase_columns_sum_to_wall_time(tmp_path):
    """Acceptance (a): under --obs the phase columns sum to within 10%
    of the measured wall time, and the span timeline lands in
    --traceDir."""
    out = _perf_run(tmp_path, obs_on=True)
    s = (out["data_wait_s"] + out["h2d_s"] + out["dispatch_s"]
         + out["device_s"] + out["ckpt_s"])
    assert s == pytest.approx(out["seconds"], rel=0.10)
    assert out["stall_frac"] is not None
    assert out["obs"]["span_events"] > 0
    with open(out["obs"]["trace_json"]) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"dispatch", "device"} <= names
    # and the scrape surface carries the step-phase histograms
    page = obs.get_registry().render()
    assert "train_phase_dispatch_ms_bucket" in page


def test_perf_obs_off_identical_modulo_null_columns(tmp_path):
    """Acceptance: an obs-off run's JSON is the pre-PR schema plus
    exactly the null phase columns."""
    out = _perf_run(tmp_path, obs_on=False)
    cols = ("data_wait_s", "h2d_s", "dispatch_s", "device_s", "ckpt_s",
            "stall_frac")
    for c in cols:
        assert c in out and out[c] is None
    assert "obs" not in out
    # no tracer was installed through the whole run
    assert not obs.enabled()


def test_install_observability_wiring(tmp_path):
    import argparse

    from bigdl_tpu.cli import common

    p = argparse.ArgumentParser()
    common.add_obs_args(p)
    # nothing set -> no-op
    args = p.parse_args([])
    assert common.install_observability(args) is None
    assert not obs.enabled()
    # --traceSteps without --traceDir is a clean CLI error
    args = p.parse_args(["--traceSteps", "2@1"])
    with pytest.raises(SystemExit, match="traceDir"):
        common.install_observability(args)
    assert not obs.enabled()
    # --traceDir implies spans + capture controller
    args = p.parse_args(["--traceDir", str(tmp_path / "t")])
    st = common.install_observability(args)
    assert st is not None and st.enabled and obs.enabled()
    assert st.capture is not None and st.capture.trace_dir == str(
        tmp_path / "t")
    st.capture.finish()
    info = st.finalize()
    assert info is st.finalize()  # idempotent
