"""The reader of the routed layer's row kernels (``moe_rows_time_share``,
``metrics/moe_rows_time_share.py``) on a synthetic trace reduced by
``lib/trace.py``: the two kernels' ops by the names their ``pallas_call``s
give, forward and transposes under the same two names; nothing from a
program without them (the parent's); its two manifest entries."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import trace  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def reader():
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_moe_rows_time_share", os.path.join(
            ROOT, "benchmark", "metrics", "moe_rows_time_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def op(name, shape, start_us, dur_us, kind=""):
    """One event of the device's op line, named as the TPU names it: the
    whole HLO instruction."""
    text = (f"%{name}.7 = {shape}{{1,0}} custom-call(%p.1), "
            f"custom_call_target=\"tpu_custom_call\"{kind}")
    return (text, start_us * 1000, dur_us * 1000)


def planes(events):
    """As ``trace.stop_and_load`` hands them on: each op labelled."""
    events = [(trace.op_label(name), s, d) for name, s, d in events]
    return [{"name": trace.DEVICE_PREFIX + "0",
             "lines": [{"name": trace.OP_LINE, "events": events}]}]


def test_the_share_of_the_two_kernels_in_a_synthetic_trace():
    events = [op("moe_dispatch_rows", "bf16[32768,2048]", 0, 100),
              op("gmm", "f32[32768,3072]", 100, 500),
              op("moe_combine_rows", "f32[8192,2048]", 600, 150),
              # the backward: each kernel's transpose is the other kernel
              op("moe_dispatch_rows", "f32[32768,2048]", 750, 200),
              op("moe_combine_rows", "f32[8192,2048]", 950, 50),
              ("%fusion.3 = f32[8192,2048]{1,0} fusion(%a), kind=kLoop",
               1_000_000, 1_000_000)]
    reduced = trace.reduce(planes(events))
    assert reduced["busy_s"] == pytest.approx(2e-3)
    assert reduced["op_seconds"]["moe_dispatch_rows bf16[32768,2048]"] == \
        pytest.approx(1e-4)
    got = reader().read({"reduced": reduced})
    assert got == pytest.approx(100 * 500e-6 / 2e-3)


@pytest.mark.parametrize("reduced", [
    None,
    {"busy_s": 0.0, "op_seconds": {"moe_combine_rows f32[8,8]": 1.0}},
    # the parent: the glue is XLA's, no kernel of these names
    {"busy_s": 2.0, "op_seconds": {"fusion/kCustom f32[32768,2048]": 0.6,
                                   "gmm f32[32768,3072]": 0.3}}],
    ids=["no_trace", "no_busy_time", "parent"])
def test_it_reads_nothing_without_the_kernels(reduced):
    assert reader().read({"reduced": reduced}) is None


@pytest.mark.parametrize("suffix,cell,moves", [
    ("glm", "train_glm_4k", "train_tok_s"),
    ("longmix", "serve_longmix_batch", "serve_tok_s")])
def test_manifest_entries(suffix, cell, moves):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == "moe_rows_time_share." + suffix]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "moe_rows_time_share." + suffix, "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": "model code nn/ models/", "moves": moves}
    assert cell in entry["workloads"]
