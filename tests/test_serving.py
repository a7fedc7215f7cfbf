"""Serving subsystem tests (ISSUE 5): bucket-padding parity, KV-cache
decode parity vs the full-sequence forward, micro-batcher flush/admission
semantics under an injected clock, metrics histogram correctness,
inference-only checkpoint restore, and an end-to-end CPU smoke of the
`serve` HTTP surface (the acceptance contract: /generate tokens
bit-identical to an offline full-sequence argmax decode, /metrics
non-zero counters)."""

import json
import os
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import models, nn
from bigdl_tpu.serving import (AdmissionError, DecodeEngine,
                               InferenceEngine, MetricsRegistry,
                               MicroBatcher, power_of_two_buckets)
from bigdl_tpu.serving.metrics import Histogram


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def tiny_net():
    m = nn.Sequential(nn.Linear(12, 16), nn.ReLU(), nn.Linear(16, 7),
                      nn.LogSoftMax())
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_lm():
    m = models.transformer_lm(50, d_model=32, num_layers=2, num_heads=2,
                              max_len=64)
    return m, m.init(jax.random.PRNGKey(1))


def _offline_greedy(model, params, prompt, n):
    """The reference decode: full-sequence forward, argmax the last
    position, append, repeat — no cache, no padding."""
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        logp, _ = model.apply(params, model.init_state(),
                              np.asarray([seq], np.int32))
        tok = int(np.argmax(np.asarray(logp)[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


# ------------------------------------------------- engine: bucket padding
def test_bucket_padding_parity_f32(tiny_net):
    model, params = tiny_net
    eng = InferenceEngine(model, params, buckets=(8,))
    x = np.random.RandomState(0).randn(5, 12).astype(np.float32)
    got = eng.predict_scores(x)
    ref, _ = model.apply(params, model.init_state(), jnp.asarray(x),
                         training=False)
    assert got.shape == (5, 7)
    assert np.array_equal(got, np.asarray(ref))


def test_bucket_padding_parity_bf16(tiny_net):
    model, params = tiny_net
    eng = InferenceEngine(model, params, buckets=(8,),
                          compute_dtype=jnp.bfloat16)
    x = np.random.RandomState(1).randn(3, 12).astype(np.float32)
    got = eng.predict_scores(x)

    def ref_fwd(x):
        y, _ = model.apply(params, model.init_state(),
                           jnp.asarray(x).astype(jnp.bfloat16),
                           training=False)
        return np.asarray(y)

    # padding rows must not perturb real rows even in bf16 (rows are
    # independent through Linear/ReLU/LogSoftMax)
    assert np.array_equal(got, ref_fwd(x))


def test_engine_chunks_past_largest_bucket(tiny_net):
    model, params = tiny_net
    reg = MetricsRegistry()
    eng = InferenceEngine(model, params, buckets=(2, 4), metrics=reg)
    x = np.random.RandomState(2).randn(9, 12).astype(np.float32)
    got = eng.predict_scores(x)  # 4 + 4 + 1->bucket2 (1 pad row)
    ref, _ = model.apply(params, model.init_state(), jnp.asarray(x),
                         training=False)
    assert np.array_equal(got, np.asarray(ref))
    assert reg._metrics["rows_total"].value == 9
    assert reg._metrics["padded_rows_total"].value == 1
    waste = reg._metrics["padding_waste_fraction"].value
    assert abs(waste - 1 / 10) < 1e-9


def test_engine_compile_cache_bounded(tiny_net):
    model, params = tiny_net
    reg = MetricsRegistry()
    eng = InferenceEngine(model, params, buckets=(2, 8), metrics=reg)
    for n in (1, 2, 5, 7, 8, 2, 1, 6):  # many row counts, two buckets
        eng.predict_scores(
            np.random.RandomState(n).randn(n, 12).astype(np.float32))
    assert reg._metrics["compiles_total"].value == 2


def test_power_of_two_buckets():
    assert power_of_two_buckets(13) == (1, 2, 4, 8, 13)
    assert power_of_two_buckets(32) == (1, 2, 4, 8, 16, 32)
    assert power_of_two_buckets(1) == (1,)
    with pytest.raises(ValueError):
        power_of_two_buckets(0)


def test_engine_matches_classifier_path(tiny_net):
    """cli/predict.py satellite: the bucketed engine must be score-level
    identical to the old full-batch-padded Classifier."""
    from bigdl_tpu.utils import Classifier
    model, params = tiny_net
    x = np.random.RandomState(3).randn(11, 12).astype(np.float32)
    old = Classifier(model, params, batch_size=8).predict_scores(x)
    new = InferenceEngine(model, params,
                          buckets=power_of_two_buckets(8)
                          ).predict_scores(x)
    assert np.array_equal(old, new)


# ----------------------------------------------------- KV-cache decode
def test_decode_parity_per_token(tiny_lm):
    """Per-token: bucketed prefill + slot decode == full-sequence
    forward argmax at every step (the acceptance bit-identity)."""
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=2)
    prompt = [3, 1, 4, 1, 5]
    got = de.generate(prompt, 8)
    ref = _offline_greedy(model, params, prompt, 8)
    assert got == ref


def test_decode_continuous_batching_parity(tiny_lm):
    """Two concurrent requests of DIFFERENT prompt lengths share the
    decode batch and still match their individual offline decodes."""
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=2)
    f1 = de.submit([7, 8], 6)
    f2 = de.submit([1, 2, 3, 4, 5, 6, 7], 6)
    steps = 0
    while not (f1.done() and f2.done()):
        assert de.step() > 0
        steps += 1
        assert steps < 50
    assert f1.result() == _offline_greedy(model, params, [7, 8], 6)
    assert f2.result() == _offline_greedy(model, params,
                                          [1, 2, 3, 4, 5, 6, 7], 6)


def test_decode_slot_reuse_after_finish(tiny_lm):
    """A finishing request frees its slot for a waiting one (continuous
    batching); the late request still decodes exactly."""
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=1)
    f1 = de.submit([9, 9], 3)
    f2 = de.submit([2, 3, 4], 3)  # waits for the single slot
    while not f2.done():
        de.step()
    assert f1.result() == _offline_greedy(model, params, [9, 9], 3)
    assert f2.result() == _offline_greedy(model, params, [2, 3, 4], 3)


def test_decode_validates_length_budget(tiny_lm):
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=1)
    with pytest.raises(ValueError):
        de.submit(list(range(60)), 10)  # 60 + 10 > max_len 64
    with pytest.raises(ValueError):
        de.submit([], 4)


def test_decode_admission_fast_reject(tiny_lm):
    model, params = tiny_lm
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=1, max_waiting=0, metrics=reg)
    de.submit([1, 2], 2)  # occupies the only slot
    with pytest.raises(AdmissionError):
        de.submit([3, 4], 2)
    assert reg._metrics["decode_rejected_total"].value == 1


def test_decode_stall_clock_and_load_during_install(tiny_lm):
    """ISSUE 21 (found on four chips): a prefill runs under the engine
    lock inside submit(). While it does, the request must count towards
    the dp routing load (or every concurrent request piles onto one
    replica), and the watchdog's stall clock must start when work
    arrives and restart when the prefill completes — not read the idle
    loop's arbitrarily old beat as a wedged worker."""
    model, params = tiny_lm
    now = [0.0]
    de = DecodeEngine(model, params, slots=2, clock=lambda: now[0])
    seen = {}
    real_install = de._install

    def slow_install(req, slot):
        seen["load"] = de.queue_load()
        seen["age_at_start"] = de.heartbeat_age()
        now[0] += 20.0          # a bucket compile
        return real_install(req, slot)

    de._install = slow_install
    now[0] = 500.0              # idle for a long time, then work arrives
    assert de.queue_load() == 0
    de.submit([1, 2, 3], 2)
    assert seen == {"load": 1, "age_at_start": 0.0}
    assert de.busy() and de.heartbeat_age() == 0.0
    assert de.queue_load() == 1  # the slot now; not double-counted


def test_serving_prefill_buckets():
    from bigdl_tpu.ops.attention_kernel import serving_prefill_buckets
    b = serving_prefill_buckets(512, 64, True, jnp.float32)
    assert b[-1] == 512 and b[0] >= 16
    assert list(b) == sorted(set(b))
    assert serving_prefill_buckets(64, 64)[-1] == 64


# ------------------------------------------------------------ batcher
def _sum_predict(batch):
    return batch.sum(axis=tuple(range(1, batch.ndim)))[:, None]


def test_batcher_max_wait_trigger_injected_clock():
    t = [0.0]
    b = MicroBatcher(_sum_predict, max_batch=4, max_wait_ms=10,
                     clock=lambda: t[0], start=False)
    futs = [b.submit(np.full(3, i, np.float32)) for i in range(3)]
    assert b.pump(0.0) == 0          # neither trigger fired
    assert b.pump(0.0099) == 0       # just under max_wait
    assert b.pump(0.0101) == 3       # oldest aged past max_wait
    assert [f.result(0) [0] for f in futs] == [0.0, 3.0, 6.0]


def test_batcher_max_batch_trigger_injected_clock():
    t = [0.0]
    b = MicroBatcher(_sum_predict, max_batch=2, max_wait_ms=1000,
                     clock=lambda: t[0], start=False)
    f1 = b.submit(np.ones(3, np.float32))
    assert b.pump(0.0) == 0
    f2 = b.submit(np.ones(3, np.float32))
    assert b.pump(0.0) == 2          # full batch flushes with zero age
    f3 = b.submit(np.ones(3, np.float32))
    assert b.pump(0.0) == 0          # the straggler waits again
    assert f1.result(0)[0] == 3.0 and f2.result(0)[0] == 3.0
    assert not f3.done()


def test_batcher_admission_fast_reject():
    reg = MetricsRegistry()
    b = MicroBatcher(_sum_predict, max_batch=4, max_queue=2,
                     clock=lambda: 0.0, start=False, metrics=reg)
    b.submit(np.ones(3))
    b.submit(np.ones(3))
    with pytest.raises(AdmissionError):
        b.submit(np.ones(3))
    assert reg._metrics["batcher_rows_rejected_total"].value == 1
    assert reg._metrics["batcher_rows_submitted_total"].value == 2
    assert b.queue_depth == 2


def test_batcher_propagates_engine_errors():
    def boom(batch):
        raise RuntimeError("engine down")
    t = [1.0]
    b = MicroBatcher(boom, max_batch=1, max_wait_ms=0,
                     clock=lambda: t[0], start=False)
    fut = b.submit(np.ones(2))
    b.pump(2.0)
    with pytest.raises(RuntimeError, match="engine down"):
        fut.result(0)


def test_batcher_threaded_end_to_end(tiny_net):
    """Real worker thread + real clock: concurrent submits coalesce into
    engine batches and every future resolves."""
    model, params = tiny_net
    eng = InferenceEngine(model, params, buckets=(1, 2, 4, 8))
    reg = MetricsRegistry()
    b = MicroBatcher(eng.predict_scores, max_batch=8, max_wait_ms=20,
                     metrics=reg)
    try:
        x = np.random.RandomState(4).randn(6, 12).astype(np.float32)
        futs = [b.submit(row) for row in x]
        got = np.stack([f.result(30.0) for f in futs])
        ref, _ = model.apply(params, model.init_state(), jnp.asarray(x),
                             training=False)
        assert np.array_equal(got, np.asarray(ref))
        assert reg._metrics["batcher_flushes_total"].value >= 1
    finally:
        b.close()


# ------------------------------------------------------------- metrics
def test_histogram_quantiles():
    h = Histogram("lat", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 7.0):
        h.observe(v)
    assert h.count == 4 and abs(h.sum - 12.0) < 1e-9
    # rank interpolation: p50 lands at the (1,2] bucket's upper edge
    assert abs(h.quantile(0.5) - 2.0) < 1e-9
    assert abs(h.quantile(0.99) - 7.84) < 1e-6
    assert abs(h.quantile(0.0) - 0.0) < 1e-9
    # overflow bucket reports the observed max, not +Inf
    h.observe(20.0)
    assert h.quantile(1.0) == 20.0
    assert np.isnan(Histogram("e", bounds=(1,)).quantile(0.5))


def test_metrics_render_exposition():
    reg = MetricsRegistry(namespace="t")
    reg.counter("reqs", "requests").inc(3)
    reg.gauge("depth", fn=lambda: 7).value
    h = reg.histogram("lat_ms", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    reg.set_provenance({"model": "x", "buckets": "1,2"})
    page = reg.render()
    assert "# TYPE t_reqs counter" in page
    assert "t_reqs 3" in page
    assert "t_depth 7" in page
    assert 't_lat_ms_bucket{le="1"} 1' in page
    assert 't_lat_ms_bucket{le="+Inf"} 2' in page
    assert "t_lat_ms_count 2" in page
    assert 't_lat_ms{quantile="0.5"}' in page
    prov_lines = [l for l in page.splitlines()
                  if l.startswith("# provenance ")]
    assert len(prov_lines) == 1
    assert json.loads(prov_lines[0][len("# provenance "):]) == {
        "model": "x", "buckets": "1,2"}
    assert 't_info{buckets="1,2",model="x"} 1' in page


def test_metrics_render_with_empty_histogram():
    """An endpoint nobody hit yet must not break the scrape: empty
    histogram quantiles render as NaN, not a handler crash (the lenet5
    smoke regression — /metrics after /predict only, generate empty)."""
    reg = MetricsRegistry(namespace="t")
    reg.histogram("never_hit_ms")
    page = reg.render()
    assert 't_never_hit_ms{quantile="0.5"} NaN' in page


def test_metrics_type_clash_rejected():
    reg = MetricsRegistry()
    reg.counter("a")
    with pytest.raises(TypeError):
        reg.histogram("a")


# ------------------------------------------- inference-only restore
def test_restore_for_inference_from_dir(tmp_path, tiny_net):
    from bigdl_tpu.utils.file import save_pytree
    from bigdl_tpu.utils.orbax_ckpt import restore_for_inference
    model, params = tiny_net
    ck = tmp_path / "ckpt"
    ck.mkdir()
    save_pytree({"params": params, "mod_state": model.init_state(),
                 "driver": {"epoch": 1, "iteration": 3}},
                str(ck / "model.3"))
    save_pytree({"params": params, "mod_state": model.init_state(),
                 "driver": {"epoch": 2, "iteration": 9}},
                str(ck / "model.9"))
    save_pytree({"momentum": params}, str(ck / "state.9"))
    p, ms = restore_for_inference(str(ck))  # picks model.9, ignores state
    ref = jax.tree_util.tree_leaves(params)
    got = jax.tree_util.tree_leaves(p)
    assert all(np.array_equal(a, b) for a, b in zip(ref, got))


def test_restore_for_inference_missing_and_corrupt(tmp_path):
    from bigdl_tpu.utils.orbax_ckpt import restore_for_inference
    with pytest.raises(SystemExit, match="does not exist"):
        restore_for_inference(str(tmp_path / "nope"))
    bad = tmp_path / "model.1"
    bad.write_bytes(b"not a checkpoint at all")
    with pytest.raises(SystemExit, match="failed to load"):
        restore_for_inference(str(bad))
    from bigdl_tpu.utils.file import save_pytree
    state_only = tmp_path / "state.1"
    save_pytree({"momentum": {"w": np.ones(3)}}, str(state_only))
    with pytest.raises(SystemExit, match="no 'params'"):
        restore_for_inference(str(state_only))


# --------------------------------------------------- end-to-end HTTP smoke
def _post(port, path, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(port, path, timeout=30):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.status, r.read().decode()


def test_serve_http_end_to_end(tmp_path, tiny_lm):
    """The acceptance contract on CPU: `serve transformer_lm` answers a
    /generate whose tokens are bit-identical to the offline
    full-sequence argmax decode of the same checkpoint, /predict works
    through the micro-batcher, and /metrics reports non-zero request and
    latency counters with config provenance."""
    from bigdl_tpu.cli import common, serve as serve_cli
    from bigdl_tpu.serving import make_server
    from bigdl_tpu.utils.file import save_pytree

    model, params = tiny_lm
    ck = tmp_path / "ckpt"
    ck.mkdir()
    save_pytree({"params": params, "mod_state": model.init_state(),
                 "driver": {"epoch": 1, "iteration": 7}},
                str(ck / "model.7"))

    args = serve_cli.build_parser().parse_args(
        ["transformer_lm", "--model", str(ck), "--vocabSize", "50",
         "--dModel", "32", "--numLayers", "2", "--numHeads", "2",
         "--seq", "64", "--slots", "2", "--buckets", "1,2,4",
         "--maxWaitMs", "2", "--lint"])
    common.apply_platform(args)
    app, eng, in_shape, in_dtype = serve_cli.build_app(args)
    assert in_shape == (64,) and in_dtype == np.int32
    srv = make_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    thr = threading.Thread(target=srv.serve_forever, daemon=True)
    thr.start()
    try:
        st, body = _get(port, "/healthz")
        assert st == 200 and body == (
            '{"status": "ok", "model": "transformer_lm"}')

        prompt = [3, 1, 4, 1, 5]
        st, out = _post(port, "/generate",
                        {"tokens": prompt, "max_new_tokens": 6})
        assert st == 200
        assert out["tokens"] == _offline_greedy(model, params, prompt, 6)

        toks = np.random.RandomState(0).randint(
            0, 50, (3, 64)).tolist()
        st, out = _post(port, "/predict", {"inputs": toks})
        assert st == 200
        assert np.asarray(out["predictions"]).shape == (3, 64)

        st, out = _post(port, "/generate",
                        {"tokens": [1] * 70, "max_new_tokens": 4})
        assert st == 400 and "exceeds" in out["error"]
        st, out = _post(port, "/predict", {"inputs": "garbage"})
        assert st == 400

        st, page = _get(port, "/metrics")
        assert st == 200
        prov = json.loads(
            [l for l in page.splitlines()
             if l.startswith("# provenance ")][0][len("# provenance "):])
        assert prov["model"] == "transformer_lm"
        assert prov["buckets"] == "1,2,4"
        assert prov["decode_slots"] == 2
        assert prov["bn_fused"] == "off"
        assert prov["autotune"] == "off"
        assert prov["lint"] == "0e/0w/0i"

        def metric(name):
            for line in page.splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[-1])
            return None

        assert metric("bigdl_serving_requests_generate_total") == 1
        assert metric("bigdl_serving_requests_predict_total") == 1
        assert metric("bigdl_serving_latency_generate_ms_count") == 1
        assert metric("bigdl_serving_latency_predict_ms_count") == 1
        assert metric("bigdl_serving_generated_tokens_total") == 6
        assert metric("bigdl_serving_rows_total") == 3
    finally:
        srv.shutdown()
        srv.server_close()
        app.close()
    thr.join(10.0)
    assert not thr.is_alive()


def test_serve_requires_weights():
    from bigdl_tpu.cli import serve as serve_cli
    args = serve_cli.build_parser().parse_args(["lenet5"])
    with pytest.raises(SystemExit, match="needs weights"):
        serve_cli.build_app(args)
