"""Per-request serving observability (ISSUE 15): lifecycle records,
TTFT/TPOT math, the flight-recorder ring, SLO burn accounting,
deterministic access-log sampling, /debug endpoints, and the off-mode
no-op contract."""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bigdl_tpu import models  # noqa: E402
from bigdl_tpu.obs import spans  # noqa: E402
from bigdl_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from bigdl_tpu.serving import (AccessLog, DecodeEngine,  # noqa: E402
                               MicroBatcher, RequestTracer, ServingApp,
                               SloPolicy, mint_rid, sanitize_rid,
                               set_request_tracer)
from bigdl_tpu.serving.reqtrace import TERMINAL_STATES  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test leaves the process-global request tracer and obs
    tracer uninstalled (the off-mode default other test files assume)."""
    yield
    set_request_tracer(None)
    spans.set_tracer(None)


@pytest.fixture(scope="module")
def tiny_lm():
    model = models.transformer_lm(50, d_model=32, num_layers=2,
                                  num_heads=2, max_len=64)
    params = model.init(jax.random.PRNGKey(1))
    return model, params


def _drive_finished(rt, t, rid="r-0", rounds=4, gap=0.010):
    """Admit -> queue -> dequeue -> prefill -> `rounds` one-token decode
    rounds `gap` apart -> finished, on the injected clock `t`."""
    rt.admit("generate", rid, prompt_tokens=5, max_new=rounds)
    t[0] += 0.010
    rt.note_queued(rid)
    t[0] += 0.010
    rt.note_dequeued(rid)
    rt.note_prefill(rid, t[0], t[0] + 0.030, slot=1)
    t[0] += 0.030
    for _ in range(rounds):
        t[0] += gap
        rt.note_round(rid, 1)
    t[0] += 0.005
    rt.finish(rid, "finished")


# --------------------------------------------------- latency definitions
def test_latency_math_injected_clock():
    """TTFT = admit -> first token; TPOT = (last-first)/(n-1); the
    queue/prefill/decode decomposition sums to ~total (ISSUE 15
    acceptance, exact under a fake clock)."""
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0])
    _drive_finished(rt, t, rid="r-0", rounds=4, gap=0.010)
    (rec,) = rt.recent()
    assert rec.state == "finished" and rec.status == 200
    assert rec.queue_wait_ms() == pytest.approx(10.0)
    assert rec.prefill_ms() == pytest.approx(30.0)
    # first token lands one gap after prefill end: TTFT = 10+10+30+10
    assert rec.ttft_ms() == pytest.approx(60.0)
    assert rec.tpot_ms() == pytest.approx(10.0)
    assert rec.decode_ms() == pytest.approx(40.0)
    assert rec.total_ms() == pytest.approx(95.0)
    assert rec.tokens_out == 4 and rec.slot == 1
    # decomposition ~ wall: queue + prefill + decode <= total
    assert (rec.queue_wait_ms() + rec.prefill_ms() + rec.decode_ms()
            <= rec.total_ms())
    assert reg._metrics["ttft_ms"]._count == 1
    assert reg._metrics["ttft_ms"]._sum == pytest.approx(60.0)
    assert reg._metrics["tpot_ms"]._sum == pytest.approx(10.0)
    assert reg._metrics["request_total_ms"]._sum == pytest.approx(95.0)
    page = reg.render()
    assert 'ttft_ms{quantile="0.5"}' in page
    assert 'tpot_ms{quantile="0.95"}' in page
    assert "requests_state_finished_total 1" in page


def test_itl_per_token_samples():
    """A k-token (speculative) round contributes k ITL samples of
    gap/k — per-token inter-token latency, not per-round."""
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0])
    rt.admit("generate", "r-itl")
    rt.note_prefill("r-itl", 0.0, 0.0)
    t[0] += 0.001
    rt.note_round("r-itl", 1)          # first token: no gap yet
    t[0] += 0.009
    rt.note_round("r-itl", 3, accepted=3)  # 9 ms round, 3 tokens
    rt.finish("r-itl", "finished")
    h = reg._metrics["itl_ms"]
    assert h._count == 3               # 3 samples from the 3-token round
    assert h._sum == pytest.approx(9.0)  # each 3 ms
    (rec,) = rt.recent()
    assert rec.tokens_out == 4 and rec.accepted_total == 3


def test_predict_ttft_stand_in():
    """/predict has no token stream: response-ready time stands in for
    first-token so TTFT still populates."""
    t = [0.0]
    rt = RequestTracer(metrics=MetricsRegistry(), clock=lambda: t[0])
    rt.admit("predict", "r-p")
    t[0] += 0.040
    rt.finish("r-p", "finished")
    (rec,) = rt.recent()
    assert rec.ttft_ms() == pytest.approx(40.0)
    assert rec.tpot_ms() is None


# ------------------------------------------------------- terminal states
def test_every_terminal_state_counted_and_statused():
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0])
    for i, (st, code) in enumerate(sorted(TERMINAL_STATES.items())):
        rid = f"r-{i}"
        rt.admit("generate", rid)
        rt.finish(rid, st)
        rec = rt.recent()[-1]
        assert rec.state == st and rec.status == code, (st, rec.status)
        assert reg._metrics[f"requests_state_{st}_total"].value == 1
    assert rt.in_flight() == []
    assert len(rt.recent()) == len(TERMINAL_STATES)


def test_finish_is_idempotent_second_only_annotates():
    """The decode engine terminalizes a generate record with honest
    timings; the server's later finish() must only annotate the HTTP
    status, not double-count or rewrite the state."""
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0])
    rt.admit("generate", "r-x")
    t[0] += 0.020
    rt.finish("r-x", "finished")          # engine side
    t[0] += 0.500                          # response marshalling later
    rt.finish("r-x", "finished", status=200)  # server side
    (rec,) = rt.recent()
    assert reg._metrics["requests_state_finished_total"].value == 1
    assert rec.total_ms() == pytest.approx(20.0)  # NOT 520


def test_finish_unknown_rid_is_noop():
    rt = RequestTracer(metrics=MetricsRegistry())
    rt.finish("never-admitted", "finished")
    assert rt.recent() == []


# -------------------------------------------------- flight-recorder ring
def test_ring_bounds_and_counts_drops():
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(capacity=4, metrics=reg, clock=lambda: t[0])
    for i in range(10):
        rid = f"r-{i:02d}"
        rt.admit("predict", rid)
        rt.finish(rid, "finished")
    recs = rt.recent()
    assert len(recs) == 4
    assert [r.rid for r in recs] == ["r-06", "r-07", "r-08", "r-09"]
    assert rt.dropped == 6
    assert reg._metrics["reqtrace_records_dropped_total"].value == 6
    snap = rt.snapshot()
    assert snap["dropped"] == 6 and snap["capacity"] == 4


def test_snapshot_schema_live_and_done():
    t = [0.0]
    rt = RequestTracer(metrics=MetricsRegistry(), clock=lambda: t[0],
                       slo=SloPolicy({"ttft": 100.0}))
    rt.admit("generate", "r-live", prompt_tokens=3, max_new=8)
    rt.note_prefill("r-live", 0.0, 0.01, slot=0)
    t[0] += 0.05
    rt.note_round("r-live", 1)
    _drive_finished(rt, t, rid="r-done")
    snap = rt.snapshot()
    assert snap["enabled"] is True
    (live,) = snap["in_flight"]
    assert live["rid"] == "r-live" and live["state"] == "decode"
    assert live["tokens_out"] == 1 and "age_ms" in live
    (done,) = snap["recent"]
    assert done["rid"] == "r-done" and done["state"] == "finished"
    for k in ("ttft_ms", "tpot_ms", "queue_wait_ms", "prefill_ms",
              "decode_ms", "total_ms", "status"):
        assert k in done, k
    assert set(snap["slo"]) >= {"targets", "burn", "window", "burn_rate",
                                "goodput_frac", "shedding"}
    json.dumps(snap)  # JSON-safe end to end


# ------------------------------------------------------------------- SLO
def test_slo_parse_and_validation():
    p = SloPolicy.parse("ttft=200, tpot=30, burn=0.8, window=16")
    assert p.targets == {"ttft": 200.0, "tpot": 30.0}
    assert p.burn == 0.8 and p.window == 16
    with pytest.raises(ValueError, match="unknown SLO dim"):
        SloPolicy.parse("p99=5")
    with pytest.raises(ValueError, match="dim=value"):
        SloPolicy.parse("ttft")
    with pytest.raises(ValueError, match="no dims"):
        SloPolicy.parse("burn=0.5")
    with pytest.raises(ValueError, match="> 0"):
        SloPolicy.parse("ttft=0")
    with pytest.raises(ValueError, match="burn"):
        SloPolicy(targets={"ttft": 1.0}, burn=1.5)


def test_slo_burn_gate_and_shed():
    """No shedding below MIN_BURN_SAMPLES; saturated burn sheds; a
    recovering window un-sheds."""
    p = SloPolicy({"ttft": 100.0}, burn=0.5, window=8)
    for _ in range(SloPolicy.MIN_BURN_SAMPLES - 1):
        p.account(False)
        assert not p.should_shed()     # gate: too few samples
    p.account(False)
    assert p.burn_rate() == 1.0 and p.should_shed()
    for _ in range(8):                 # window slides to all-good
        p.account(True)
    assert p.burn_rate() == 0.0 and not p.should_shed()
    assert p.goodput_frac() == pytest.approx(8 / 16)


def test_slo_counters_only_finished_requests():
    """SLO evaluation covers only 'finished' requests — a shed request
    cannot also count as an SLO violation."""
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0],
                       slo=SloPolicy.parse("ttft=60"))
    _drive_finished(rt, t, rid="r-good", rounds=1, gap=0.001)  # ttft 51
    rt.admit("generate", "r-shed")
    rt.finish("r-shed", "shed")
    rt.admit("generate", "r-slow")
    t[0] += 0.500
    rt.note_round("r-slow", 1)         # ttft 500 ms > 50
    rt.finish("r-slow", "finished")
    assert reg._metrics["slo_requests_total"].value == 2
    assert reg._metrics["slo_good_total"].value == 1
    assert reg._metrics["slo_violations_total"].value == 1
    assert reg._metrics["slo_ttft_violations_total"].value == 1


# ------------------------------------------------------------ access log
def test_access_log_writes_jsonl(tmp_path):
    t = [0.0]
    path = str(tmp_path / "access.jsonl")
    rt = RequestTracer(metrics=MetricsRegistry(), clock=lambda: t[0],
                       access_log=AccessLog(path))
    _drive_finished(rt, t, rid="r-a")
    rt.admit("generate", "r-b")
    rt.finish("r-b", "expired", error="deadline")
    rt.close()
    recs = [json.loads(l) for l in open(path)]
    assert [r["rid"] for r in recs] == ["r-a", "r-b"]
    assert recs[0]["state"] == "finished" and recs[0]["ttft_ms"] == 60.0
    assert recs[1]["state"] == "expired" and recs[1]["status"] == 504
    assert recs[1]["error"] == "deadline"


def test_access_log_sampling_deterministic(tmp_path):
    """sha256(rid)-keyed sampling: the same rids are kept on every run,
    the keep fraction tracks the probability, and 0/1 are exact."""
    rids = [f"req-{i:04d}" for i in range(400)]
    a = AccessLog(str(tmp_path / "a.jsonl"), sample=0.25)
    b = AccessLog(str(tmp_path / "b.jsonl"), sample=0.25)
    kept_a = {r for r in rids if a.sampled(r)}
    kept_b = {r for r in rids if b.sampled(r)}
    assert kept_a == kept_b            # deterministic, not RNG
    assert 50 <= len(kept_a) <= 150    # ~100 of 400
    full = AccessLog(str(tmp_path / "c.jsonl"), sample=1.0)
    none = AccessLog(str(tmp_path / "d.jsonl"), sample=0.0)
    assert all(full.sampled(r) for r in rids)
    assert not any(none.sampled(r) for r in rids)
    for log in (a, b, full, none):
        log.close()
    with pytest.raises(ValueError):
        AccessLog(str(tmp_path / "e.jsonl"), sample=1.5)


def test_access_log_sampled_out_counter(tmp_path):
    log = AccessLog(str(tmp_path / "s.jsonl"), sample=0.5)
    rids = [f"req-{i}" for i in range(100)]
    for r in rids:
        log.write({"rid": r})
    assert log.lines + log.sampled_out == 100
    assert log.lines == sum(1 for _ in open(log.path))
    log.close()


# ---------------------------------------------------------- request ids
def test_mint_and_sanitize_rid():
    a, b = mint_rid(), mint_rid()
    assert a != b and sanitize_rid(a) == a
    assert sanitize_rid("client-id-42") == "client-id-42"
    assert sanitize_rid(None) is None
    assert sanitize_rid("") is None
    assert sanitize_rid("has space") is None
    assert sanitize_rid("tab\tchar") is None
    assert sanitize_rid("x" * 65) is None
    assert sanitize_rid("x" * 64) == "x" * 64
    assert sanitize_rid("café") is None  # non-ASCII


# ------------------------------------------------- obs.spans timeline
def test_finished_request_writes_nothing_onto_the_obs_timeline():
    """A request's phases are live ``obs.spans`` spans carrying its rid;
    the recorder back-dates no copies into the ring, shared clock or
    not."""
    t = [0.0]
    tr = spans.Tracer(clock=lambda: t[0])
    spans.set_tracer(tr)
    rt = RequestTracer(metrics=MetricsRegistry(), clock=tr.clock)
    _drive_finished(rt, t)
    assert tr.events() == []


# --------------------------------- batcher: per-row queue wait + threading
def test_batcher_per_row_queue_wait_spans():
    """ISSUE 15 satellite fix: EVERY row's queue wait lands on the
    timeline, not just the oldest's."""
    t = [0.0]
    tr = spans.Tracer(clock=lambda: t[0])
    spans.set_tracer(tr)
    b = MicroBatcher(lambda x: x.sum(axis=1)[:, None], max_batch=4,
                     max_wait_ms=10, clock=lambda: t[0], start=False)
    b.submit(np.zeros(3, np.float32))
    t[0] = 0.005
    b.submit(np.ones(3, np.float32))
    t[0] = 0.011
    assert b.pump(t[0]) == 2
    waits = [e for e in tr.events() if e["name"] == "queue_wait"]
    assert len(waits) == 2             # one PER ROW
    durs = sorted(round(e["dur"], 6) for e in waits)
    assert durs == [0.006, 0.011]
    assert all(e["args"]["rows"] == 2 for e in waits)


def test_batcher_threads_rids_through_lifecycle():
    t = [0.0]
    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg, clock=lambda: t[0])
    set_request_tracer(rt)

    def fn(x, rids=None):              # engine-style signature
        assert rids == ["r-0", None]   # untagged rows stay None
        return x.sum(axis=1)[:, None]

    b = MicroBatcher(fn, max_batch=2, max_wait_ms=1000,
                     clock=lambda: t[0], start=False)
    rt.admit("predict", "r-0")
    b.submit(np.zeros(3, np.float32), rid="r-0")
    b.submit(np.ones(3, np.float32))   # rid-less submit still fine
    t[0] = 0.008
    assert b.pump(t[0]) == 2
    rt.finish("r-0", "finished")
    (rec,) = rt.recent()
    assert rec.queue_wait_ms() == pytest.approx(8.0)


# ------------------------------------------ decode engine: lifecycle e2e
def test_decode_lifecycle_finished(tiny_lm):
    """A traced /generate request: record walks admitted -> decode ->
    finished with tokens, rounds, slot, and a sane timing decomposition
    — and the traced output is bit-identical to the untraced one."""
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=2)
    prompt = [3, 1, 4, 1, 5]
    ref = de.generate(prompt, 6)       # untraced reference

    reg = MetricsRegistry()
    rt = RequestTracer(metrics=reg)
    set_request_tracer(rt)
    rt.admit("generate", "r-gen", prompt_tokens=len(prompt), max_new=6)
    fut = de.submit(prompt, 6, rid="r-gen")
    steps = 0
    while not fut.done():
        de.step()
        steps += 1
        assert steps < 50
    assert fut.result() == ref         # tracing never changes tokens
    (rec,) = rt.recent()
    assert rec.state == "finished" and rec.status == 200
    assert rec.tokens_out == 6 and rec.round_count == 6
    assert rec.slot in (0, 1)
    assert rec.prefill_ms() > 0 and rec.decode_ms() > 0
    assert rec.ttft_ms() > 0 and rec.tpot_ms() > 0
    assert reg._metrics["requests_state_finished_total"].value == 1
    h = reg._metrics["itl_ms"]
    assert h._count == 5               # 6 tokens -> 5 gaps


def test_decode_lifecycle_expired_in_queue(tiny_lm):
    model, params = tiny_lm
    t = [0.0]
    de = DecodeEngine(model, params, slots=1, clock=lambda: t[0])
    rt = RequestTracer(metrics=MetricsRegistry())
    set_request_tracer(rt)
    rt.admit("generate", "r-hold")
    hold = de.submit([9, 9], 30, rid="r-hold")  # pins the only slot
    de.step()
    rt.admit("generate", "r-late")
    late = de.submit([2, 3], 4, deadline=1.0, rid="r-late")
    t[0] = 2.0                         # past the deadline
    de.step()
    assert late.done()
    rec = {r.rid: r for r in rt.recent()}["r-late"]
    assert rec.state == "expired" and rec.status == 504
    assert "queue" in rec.error
    while not hold.done():
        de.step()


def test_decode_lifecycle_closed(tiny_lm):
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=1)
    rt = RequestTracer(metrics=MetricsRegistry())
    set_request_tracer(rt)
    rt.admit("generate", "r-c1")
    rt.admit("generate", "r-c2")
    de.submit([1, 2], 20, rid="r-c1")
    de.step()                          # r-c1 active, r-c2 waiting
    de.submit([3, 4], 20, rid="r-c2")
    de.close()
    states = {r.rid: r.state for r in rt.recent()}
    assert states == {"r-c1": "closed", "r-c2": "closed"}


def test_decode_debug_snapshot_schema(tiny_lm):
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=2, kv_page_tokens=16)
    fut = de.submit([5, 6, 7], 8, rid="r-snap")
    de.step()
    snap = de.debug_snapshot()
    assert snap["slots_total"] == 2 and snap["slots_active"] == 1
    active = [s for s in snap["slots"] if s["state"] == "active"]
    free = [s for s in snap["slots"] if s["state"] == "free"]
    assert len(active) == 1 and len(free) == 1
    assert active[0]["rid"] == "r-snap"
    assert active[0]["prompt_tokens"] == 3
    assert active[0]["pages"] >= 1
    kv = snap["kv"]
    assert kv["paged"] is True and kv["page_tokens"] == 16
    assert kv["pages_in_use"] >= 1
    assert 0.0 < kv["occupancy_frac"] <= 1.0
    while not fut.done():
        de.step()
    snap = de.debug_snapshot()
    assert snap["slots_active"] == 0
    json.dumps(snap)


# -------------------------------------------------- /debug via ServingApp
def test_debug_endpoints_via_app(tiny_lm):
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=2)
    b = MicroBatcher(lambda x: x, max_batch=2, start=False)
    app = ServingApp(name="transformer_lm", metrics=MetricsRegistry(),
                     batcher=b, decoder=de)
    # tracer off: /debug/requests is an honest 404, /debug/slots works
    st, body = app.handle_debug_requests()
    assert st == 404 and body["enabled"] is False
    st, body = app.handle_debug_slots()
    assert st == 200
    assert body["batcher"]["queue_depth"] == 0
    assert body["batcher"]["max_queue"] == 256
    # tracer on: full snapshot
    rt = RequestTracer(metrics=MetricsRegistry())
    set_request_tracer(rt)
    rt.admit("generate", "r-dbg")
    st, body = app.handle_debug_requests()
    assert st == 200 and body["enabled"] is True
    assert body["in_flight"][0]["rid"] == "r-dbg"
    de.close()


def test_dispatch_terminalizes_shed_and_errors(tiny_lm):
    """dispatch_post opens a record at admission and terminalizes every
    exit: a shed /generate leaves a 'shed' autopsy record."""
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=1, max_waiting=4)
    app = ServingApp(name="transformer_lm", metrics=MetricsRegistry(),
                     decoder=de, shed_generate_frac=0.75)
    rt = RequestTracer(metrics=MetricsRegistry(),
                       slo=SloPolicy({"ttft": 0.0001}, burn=0.5,
                                     window=8))
    set_request_tracer(rt)
    # before the burn saturates: a malformed body is a bad_request
    # autopsy record, not a shed
    st, _ = app.dispatch_post("/generate", {"tokens": "bad"},
                              rid="r-bad")
    assert st == 400
    rec = {r.rid: r for r in rt.recent()}["r-bad"]
    assert rec.state == "bad_request" and rec.status == 400
    for _ in range(SloPolicy.MIN_BURN_SAMPLES):  # saturate the burn
        rt.admit("generate", rid := mint_rid())
        rt.note_round(rid, 1)
        rt.finish(rid, "finished")
    assert rt.slo.should_shed()
    st, body = app.dispatch_post("/generate",
                                 {"tokens": [1, 2], "max_new_tokens": 2},
                                 rid="r-shed")
    assert st == 429
    rec = {r.rid: r for r in rt.recent()}["r-shed"]
    assert rec.state == "shed" and rec.status == 429
    de.close()


# ------------------------------------------------------ off-mode contract
def test_off_mode_is_noop(tiny_lm):
    """No tracer installed: rid-tagged submits behave exactly like
    untagged ones and nothing records anywhere (the --reqTrace off
    byte-identical contract)."""
    from bigdl_tpu.serving import reqtrace
    assert reqtrace.get() is None
    model, params = tiny_lm
    de = DecodeEngine(model, params, slots=1)
    fut = de.submit([3, 1, 4], 5, rid="r-ignored")
    while not fut.done():
        de.step()
    assert fut.result() == de.generate([3, 1, 4], 5)
    b = MicroBatcher(lambda x: x.sum(axis=1)[:, None], max_batch=1,
                     max_wait_ms=0, clock=lambda: 0.0, start=False)
    f = b.submit(np.ones(3, np.float32), rid="r-also-ignored")
    b.pump(1.0)
    assert f.result(0)[0] == 3.0
    de.close()
