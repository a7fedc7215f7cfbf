"""The readers of the stream writer: ``stream_writer_cpu_ms``
and ``stream_frames_per_pass``. ``None`` on a registry without the
writer's counters (the parent, which counts its rounds and its handlers'
CPU), the defined ratio on a synthetic one, and on the real HTTP front
after streamed requests a value for each."""

import http.client
import importlib.util
import json
import os
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ["stream_writer_cpu_ms", "stream_frames_per_pass"]

# 4 rounds; the writer 0.006 s of CPU over 5 passes that wrote 180 frames
COUNTS = {"decode_rounds_total": 4,
          "stream_handler_cpu_seconds_total": 0.032,
          "stream_writer_cpu_seconds_total": 0.006,
          "stream_frames_total": 180,
          "stream_writer_passes_total": 5}
EXPECTED = {"stream_writer_cpu_ms": 1e3 * 0.006 / 4,
            "stream_frames_per_pass": 180 / 5}


def reader(base):
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + base,
        os.path.join(ROOT, "benchmark", "metrics", base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


@pytest.mark.parametrize("base", READERS)
def test_reader_gives_none_without_the_counters(base, registry):
    assert reader(base)({}) is None
    # the parent counts its rounds and its handlers' CPU, not the writer
    registry.counter("decode_rounds_total").inc(40)
    registry.counter("stream_handler_cpu_seconds_total").inc(0.5)
    assert reader(base)({"planes": None}) is None


@pytest.mark.parametrize("base", READERS)
def test_reader_on_a_counted_registry(base, registry):
    for name, value in COUNTS.items():
        registry.counter(name).inc(value)
    assert reader(base)({}) == pytest.approx(EXPECTED[base])


def test_the_writer_cpu_reads_nothing_before_the_first_round(registry):
    registry.counter("stream_writer_cpu_seconds_total").inc(0.001)
    assert reader("stream_writer_cpu_ms")({}) is None


def test_the_readers_on_the_real_front(registry):
    """Two streamed requests through the toy LM, one after the other:
    the writer's CPU a round is positive, and a pass wrote a frame or
    more (one stream at a time: about one a pass)."""
    import jax

    from bigdl_tpu import models
    from bigdl_tpu.serving import DecodeEngine, ServingApp, make_server

    model = models.transformer_lm(50, d_model=32, num_layers=2,
                                  num_heads=2, max_len=64)
    de = DecodeEngine(model, model.init(jax.random.PRNGKey(1)), slots=2,
                      max_len=64, metrics=registry)
    app = ServingApp(name="toy", metrics=registry, decoder=de)
    srv = make_server(app, "127.0.0.1", 0)
    de.start()
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        for prompt in ([3, 1, 4], [2, 7, 1, 8]):
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.server_address[1], timeout=120)
            conn.request("POST", "/generate", json.dumps(
                {"tokens": prompt, "max_new_tokens": 4, "stream": True}))
            assert '"done": true' in conn.getresponse().read().decode()
            conn.close()
    finally:
        app.close()  # joins the writer: its counters are final
        srv.shutdown()
        srv.server_close()
        de.close()
    assert reader("stream_writer_cpu_ms")({}) > 0.0
    assert 1.0 <= reader("stream_frames_per_pass")({}) <= 5.0
