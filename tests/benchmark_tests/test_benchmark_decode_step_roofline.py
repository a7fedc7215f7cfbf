"""``decode_step_roofline``: the hand-computed share on a made-up run
(known counters, a known program time), and nothing without a trace, a
peak table, the program on the trace or the program's counter (the parent
of the PR that added it)."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "benchmark_metric_decode_step_roofline", os.path.join(
        ROOT, "benchmark", "metrics", "decode_step_roofline.py"))
roofline = importlib.util.module_from_spec(spec)
spec.loader.exec_module(roofline)

MODEL = {"vocab": 128, "d_model": 64, "num_layers": 2, "num_heads": 4,
         "d_ff": 256, "max_len": 128, "num_kv_heads": 2}
# weights in a matmul: 2 layers x (2 x 64 x 64 + 2 x 64 x 32 + 2 x 64 x 256)
# + the 64 x 128 head = 98,304, at 2 bytes; a cached position: K and V x
# 2 heads x 16 x 2 bytes x 2 layers = 256 bytes, 300 of them live a step
LEAST_BYTES = 2 * 98_304 + 300 * 256


def made_up_run(**over):
    run = {"config": {"model": MODEL, "serve": {"programs": {
               "decode_step": "jit__one", "prefill": "jit__prefill"}}},
           "peaks": {"hbm_bytes_per_s": 1e9},
           "reduced": {"modules": {
               "jit__one(123)": {"count": 4.0, "seconds": 0.004},
               "jit__prefill(9)": {"count": 1.0, "seconds": 0.5}}}}
    return dict(run, **over)


@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


def test_share_of_a_made_up_run(registry):
    registry.counter("decode_steps_total").inc(10)
    registry.counter("decode_live_positions_total").inc(3000)
    # 273,408 bytes: 273.408 us at 1 GB/s, over 1 ms a step = 27.3408 %
    assert roofline.read(made_up_run()) == pytest.approx(
        100 * (LEAST_BYTES / 1e9) / 1e-3)


@pytest.mark.parametrize("why,over,counted", [
    ("no trace", {"reduced": None}, True),
    ("no peak table (the CPU rehearsal)", {"peaks": None}, True),
    ("the step is not on the trace", {"reduced": {"modules": {
        "jit__prefill(9)": {"count": 1.0, "seconds": 0.5}}}}, True),
    ("no counter in the program", {}, False),
])
def test_nothing_without_its_input(registry, why, over, counted):
    registry.counter("decode_steps_total").inc(10)
    if counted:
        registry.counter("decode_live_positions_total").inc(3000)
    assert roofline.read(made_up_run(**over)) is None, why


MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("name,moves,cell", [
    ("decode_step_roofline.chat", "itl_p50_ms", "serve_chat_steady"),
    ("decode_step_roofline.code", "serve_tok_s", "serve_code_batch"),
])
def test_manifest_entry(name, moves, cell):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    # a share of a roofline: %, more of it is better, read off the trace
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "device_trace",
                     "layer": "engines serving/decode.py",
                     "moves": moves, "workloads": [cell]}
