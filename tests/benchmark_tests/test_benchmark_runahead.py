"""``decode_runahead_share``: its value on a counted registry, its 0 on a
registry whose engine never ran ahead, nothing while no step was counted
or where the program has no such counter (the parent of the PR that added
it), and the real engine's own counters at toy sizes."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "benchmark_metric_decode_runahead_share", os.path.join(
        ROOT, "benchmark", "metrics", "decode_runahead_share.py"))
runahead = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runahead)


@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


@pytest.mark.parametrize("steps,ahead,want", [
    (200, 188, 94.0),
    (7, 7, 100.0),
    (12, 0, 0.0),   # an engine that only ever ran one step deep
    (0, 0, None),   # no step counted yet
])
def test_share_of_a_counted_registry(registry, steps, ahead, want):
    registry.counter("decode_steps_total").inc(steps)
    registry.counter("decode_runahead_steps_total").inc(ahead)
    got = runahead.read({})
    assert got == (want if want is None else pytest.approx(want))


def test_nothing_from_a_program_without_the_counter(registry):
    registry.counter("decode_steps_total").inc(10)
    assert runahead.read({}) is None
    # and the reader did not make the counter by asking for it
    assert "decode_runahead_steps_total" not in registry.render()


def test_the_engines_own_counters(registry):
    """Caller-driven steps read 0; the same engine's thread runs ahead on
    all but the first step of an answer."""
    import jax

    from bigdl_tpu import models
    from bigdl_tpu.serving import DecodeEngine

    model = models.transformer_lm(50, d_model=32, num_layers=2,
                                  num_heads=2, max_len=64)
    eng = DecodeEngine(model, model.init(jax.random.PRNGKey(1)), slots=2,
                       prompt_buckets=(16,), metrics=registry)
    assert runahead.read({}) is None
    eng.generate([3, 1, 4, 1, 5], 10)
    assert runahead.read({}) == 0.0
    eng.start()
    try:
        eng.generate([3, 1, 4, 1, 5], 30)
    finally:
        eng.close()
    # 10 steps one deep, then 30 of which 29 ran ahead
    assert runahead.read({}) == pytest.approx(100 * 29 / 40)


MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("name,moves,cell", [
    ("decode_runahead_share.chat", "itl_p50_ms", "serve_chat_steady"),
    ("decode_runahead_share.code", "serve_tok_s", "serve_code_batch"),
    ("decode_runahead_share.reason", "serve_tok_s", "serve_reason_batch"),
])
def test_manifest_entry(name, moves, cell):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "engines serving/decode.py",
                     "moves": moves, "workloads": [cell]}
