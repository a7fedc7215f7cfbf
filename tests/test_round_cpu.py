"""The decode round by thread CPU (ISSUE 39): a span given a pair of
counters adds its wall seconds and its thread-CPU seconds to them, and
one without reads no clock; the decode engine counts its rounds and the
wall and CPU of each round, its program calls and its wait for the
device, plain and speculative, through ``step()`` and through the loop
thread; the HTTP front counts its stream handlers' CPU and its stream
writer's frames, passes and CPU. Toy sizes, CPU."""

import http.client
import json
import threading
import time

import jax
import pytest

from bigdl_tpu import models, obs
from bigdl_tpu.obs import spans
from bigdl_tpu.obs.metrics import Counter
from bigdl_tpu.serving import (DecodeEngine, MetricsRegistry, ServingApp,
                               make_server)

PAIRS = ("round", "dispatch", "read")  # decode_<pair>_[cpu_]seconds_total
PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9], [2, 7]]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    yield
    obs.disable()


class Clocks:
    """A fake pair: every read of either clock advances it by its step."""

    def __init__(self, wall_step=1.0, cpu_step=0.25):
        self.wall = self.cpu = 0.0
        self.steps = (wall_step, cpu_step)
        self.reads = 0

    def read_wall(self):
        self.reads += 1
        self.wall += self.steps[0]
        return self.wall

    def read_cpu(self):
        self.reads += 1
        self.cpu += self.steps[1]
        return self.cpu


@pytest.fixture
def clocks(monkeypatch):
    c = Clocks()
    monkeypatch.setattr(spans, "_wall_clock", c.read_wall)
    monkeypatch.setattr(spans, "_cpu_clock", c.read_cpu)
    return c


# ---------------------------------------------------------------- the span
def test_span_with_counters_adds_exactly_its_two_differences(clocks):
    wall, cpu = Counter("w"), Counter("c")
    with obs.span("phase", (wall, cpu), x=1):
        clocks.wall += 10.0  # the work inside: 10 s wall, 2 s on a CPU
        clocks.cpu += 2.0
    # entry reads wall 1 then cpu 0.25; exit reads cpu 2.5 then wall 12
    assert clocks.reads == 4
    assert wall.value == 12.0 - 1.0 and cpu.value == 2.5 - 0.25
    with obs.span("phase", (None, cpu)):  # a CPU counter alone
        pass
    assert wall.value == 11.0 and cpu.value == 2.25 + 0.25


def test_span_without_counters_reads_no_clock(clocks):
    import jax.profiler

    s = obs.span("bare", x=1)
    assert type(s) is jax.profiler.TraceAnnotation
    with s:
        pass
    obs.set_tracer(spans.Tracer(clock=lambda: 0.0))  # the ring's own clock
    with obs.span("ringed"):
        pass
    assert clocks.reads == 0


@pytest.mark.parametrize("tracer", [False, True], ids=["bare", "ring"])
def test_counted_spans_nest_and_cpu_never_exceeds_wall(tracer):
    if tracer:
        tr = obs.enable()
    outer, inner = (Counter("ow"), Counter("oc")), (Counter("iw"),
                                                     Counter("ic"))
    with obs.span("outer", outer):
        with obs.span("inner", inner):
            time.sleep(0.02)  # off the CPU
        t = time.perf_counter()
        while time.perf_counter() - t < 0.01:  # on it
            pass
    ow, oc, iw, ic = (c.value for c in (*outer, *inner))
    assert iw >= 0.02 and ic < 0.01  # the sleep is wall, not CPU
    assert 0.0 < ic <= iw < ow and ic < oc <= ow
    assert oc - ic >= 0.005  # the busy loop is the outer span's own CPU
    if tracer:
        assert [e["name"] for e in tr.events()] == ["inner", "outer"]


# -------------------------------------------------------------- the engine
def lm():
    m = models.transformer_lm(50, d_model=32, num_layers=2, num_heads=2,
                              max_len=64)
    return m, m.init(jax.random.PRNGKey(1))


def counted(registry):
    """``{pair: (wall, cpu)}`` and the round count of an engine's
    registry."""
    values = {p: (registry.counter(f"decode_{p}_seconds_total").value,
                  registry.counter(f"decode_{p}_cpu_seconds_total").value)
              for p in PAIRS}
    return values, registry.counter("decode_rounds_total").value


def assert_consistent(values):
    for wall, cpu in values.values():
        assert 0.0 <= cpu <= wall
    round_, dispatch, read = (values[p] for p in PAIRS)
    for i in (0, 1):  # wall, then CPU
        assert round_[i] >= dispatch[i] + read[i]


@pytest.mark.parametrize("speculate", [0, 3], ids=["plain", "speculative"])
def test_engine_counts_the_rounds_it_steps(speculate):
    model, params = lm()
    reg = MetricsRegistry()
    de = DecodeEngine(model, params, slots=2, max_len=64, metrics=reg,
                      speculate=speculate)
    futs = [de.submit(p, 6) for p in PROMPTS[:3]]  # the third queues
    rounds = 0
    while not all(f.done() for f in futs):
        rounds += de.step() > 0
    assert de.step() == 0  # an idle call is no round
    values, counted_rounds = counted(reg)
    assert counted_rounds == rounds > 0
    assert values["dispatch"][0] > 0.0 and values["read"][0] > 0.0
    assert_consistent(values)


def test_engine_counts_the_loop_threads_rounds():
    model, params = lm()
    reg = MetricsRegistry()
    tr = obs.enable()  # the ring counts the rounds the thread ran
    de = DecodeEngine(model, params, slots=2, max_len=64, metrics=reg)
    de.start()
    try:
        futs = [de.submit(p, 8) for p in PROMPTS]
        for f in futs:
            f.result(timeout=120)
    finally:
        de.close()
    values, counted_rounds = counted(reg)
    ring = [e for e in tr.events() if e["name"] == "decode_round"]
    assert counted_rounds == len(ring) > 0
    assert values["round"][0] == pytest.approx(
        sum(e["dur"] for e in ring), rel=0.05)
    assert_consistent(values)


def test_engine_without_a_registry_adds_no_counter(clocks):
    model, params = lm()
    de = DecodeEngine(model, params, slots=2, max_len=64)
    assert de.generate(PROMPTS[0], 4)
    assert clocks.reads == 0


# ---------------------------------------------------------------- the front
def stream_once(reg):
    """One streamed /generate through the real engine and HTTP front;
    the app, for its counters."""
    model, params = lm()
    de = DecodeEngine(model, params, slots=2, max_len=64, metrics=reg)
    app = ServingApp(name="toy", metrics=reg, decoder=de)
    srv = make_server(app, "127.0.0.1", 0)
    de.start()
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1",
                                          srv.server_address[1], timeout=120)
        conn.request("POST", "/generate", json.dumps(
            {"tokens": PROMPTS[0], "max_new_tokens": 5, "stream": True}))
        body = conn.getresponse().read().decode()
        conn.close()
        assert '"done": true' in body
        cpu = reg.counter("stream_handler_cpu_seconds_total")
        deadline = time.monotonic() + 30  # the handler ends after the frame
        while cpu.value == 0.0 and time.monotonic() < deadline:
            time.sleep(0.01)
        return app.metrics.render()
    finally:
        app.close()
        srv.shutdown()
        srv.server_close()
        de.close()


def test_streamed_generate_counts_the_handlers_cpu():
    reg = MetricsRegistry()
    text = stream_once(reg)
    assert reg.counter("stream_handler_cpu_seconds_total").value > 0.0
    for name in ("stream_handler_cpu_seconds_total",
                 "decode_rounds_total",
                 *(f"decode_{p}_{k}seconds_total" for p in PAIRS
                   for k in ("", "cpu_"))):
        assert name in text


def test_streamed_generate_counts_the_stream_writers_work():
    """The writer's three counters move on one streamed request: five
    tokens in one to five frames, and the done frame; each pass that
    wrote a frame counted, and its CPU."""
    reg = MetricsRegistry()
    text = stream_once(reg)
    frames = reg.counter("stream_frames_total").value
    passes = reg.counter("stream_writer_passes_total").value
    assert 2 <= frames <= 6 and 1 <= passes <= frames
    assert reg.counter("stream_writer_cpu_seconds_total").value > 0.0
    for name in ("stream_frames_total", "stream_writer_passes_total",
                 "stream_writer_cpu_seconds_total"):
        assert name in text
