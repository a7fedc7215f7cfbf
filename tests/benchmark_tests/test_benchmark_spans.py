"""The readers of the program's own spans and counters
(``benchmark/lib/spans.py`` and the metrics that use it): exact values on a
synthetic plane list, ``None`` where the program has no such span or
counter (the parent of the PR that added them), and a number from every
reader on the planes of the real serving stack and training loop at toy
sizes (so a span renamed on one side only is caught)."""

import importlib.util
import os

import pytest

from benchmark.lib import spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns


def reader(base):
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + base,
        os.path.join(ROOT, "benchmark", "metrics", base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def line(*events, name="python"):
    """A thread line; events are ``(name, start_ms, dur_ms)``, and a bare
    word is a span of the program."""
    return {"name": name, "events": [
        (n if ":" in n or "(" in n else "bigdl:" + n, int(s * MS),
         int(d * MS)) for n, s, d in events]}


# the decode thread: two rounds, the second held up for 6 ms; a prefill
# nested in the emit loop. Two handler lines with the same line name, the
# second serving two requests one after the other. The training loop:
# two steps and the empty pass that ends an epoch. The device plane holds
# a same-named span that no reader may count.
SYNTHETIC = [
    {"name": "/host:CPU", "lines": [
        line(("decode_lock_wait", 0, 1), ("decode_round", 1, 100),
             ("decode_step", 2, 90), ("decode_host_read", 10, 80),
             ("decode_emit", 92, 8), ("decode_prefill", 93, 5),
             ("decode_lock_wait", 101, 2), ("decode_lock_wait", 103, 4),
             ("decode_round", 107, 50), ("decode_host_read", 110, 44),
             ("np.asarray(jax.Array)", 110, 44), ("decode_idle", 157, 30)),
        line(("generate_request", 0, 400), ("generate_admit", 0.01, 30),
             ("submit_lock_wait", 1, 20), ("decode_prefill", 21, 8),
             ("generate_first_token_wait", 30.01, 120),
             ("generate_stream", 150.02, 249)),
        line(("generate_admit", 5, 12), ("submit_lock_wait", 6, 10),
             ("generate_first_token_wait", 17, 70),
             ("generate_admit", 200, 40), ("submit_lock_wait", 201, 30),
             ("generate_first_token_wait", 240, 60)),
        line(("train_step", 0, 330), ("data_wait", 0, 1), ("h2d", 1, 1),
             ("dispatch", 2, 2), ("loss_fetch", 4, 325),
             ("bench:optimizer_step", 329.5, 330),
             ("train_step", 330, 332), ("loss_fetch", 335, 323),
             ("train_step", 662, 1), ("data_wait", 662, 0.5))]},
    {"name": "/device:TPU:0", "lines": [
        line(("submit_lock_wait", 0, 999), ("decode_round", 0, 999),
             ("decode_host_read", 1, 1), name="XLA Ops")]},
]
EXPECTED = {
    "engine_lock_wait_ms": 20.0,            # median of 20, 10, 30
    "decode_loop_stall_ms": (1 + 2 + 4) / 2,  # 7 ms over 2 rounds
    "decode_host_ms": ((100 - 80) + (50 - 44)) / 2,
    "server_ttft_ms": 100.0,  # median of 150, 82, 100: admit to token
    "step_host_ms": ((330 - 325) + (332 - 323)) / 2,  # not the empty pass
}


@pytest.mark.parametrize("base", sorted(EXPECTED))
def test_span_reader_on_a_synthetic_trace(base):
    assert reader(base)({"planes": SYNTHETIC}) == pytest.approx(
        EXPECTED[base], abs=1e-3)  # the starts are whole nanoseconds


SPAN_READERS = sorted(EXPECTED)
COUNTER_READERS = ["prefill_pad_share", "queue_wait_ms"]


@pytest.fixture
def registry():
    from bigdl_tpu.obs.metrics import (MetricsRegistry, get_registry,
                                       set_registry)
    before, reg = get_registry(), MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(before)


def test_counter_readers_on_a_counted_registry(registry):
    registry.counter("prompt_tokens_total").inc(300)
    registry.counter("prefill_bucket_tokens_total").inc(400)
    registry.counter("decode_queued_total").inc(4)
    registry.counter("decode_queue_wait_seconds_total").inc(10.0)
    assert reader("prefill_pad_share")({}) == pytest.approx(25.0)
    assert reader("queue_wait_ms")({}) == pytest.approx(2500.0)


@pytest.mark.parametrize("base", SPAN_READERS + COUNTER_READERS)
def test_reader_gives_none_without_its_input(base, registry):
    """What the parent commit gives: jax's and the benchmark's own host
    events, no ``bigdl:`` span; the engine's older counters alone."""
    registry.counter("prompt_tokens_total").inc(300)
    bare = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("PjitFunction(_one)", 0, MS), ("bench:optimizer_step", 0, 9 * MS),
        ("decode_round", 0, 5 * MS)]}]}]  # a name without the tag
    for run in ({"planes": bare}, {"planes": None}, {"planes": []}, {}):
        assert reader(base)(run) is None


def test_helpers_take_children_by_containment_on_the_same_line():
    planes = [{"name": "/host:CPU", "lines": [
        line(("parent", 0, 10), ("child", 2, 3), ("child", 6, 3),
             ("child", 9, 5), ("parent", 20, 10)),
        line(("child", 1, 1))]}]  # another thread's: not this parent's
    assert spans.self_ms(planes, "parent", "child") == [4.0]
    assert spans.durations_ms(planes, "child") == [3.0, 3.0, 5.0, 1.0]
    assert spans.since_ms(planes, "parent", "child") == [
        5.0, 9.0, 14.0]  # to each child's end; the other line has no parent
    assert spans.mean([]) is None


@pytest.mark.parametrize("base", SPAN_READERS + COUNTER_READERS)
def test_reader_on_the_real_programs_planes(base, traced_toy_run):
    """The real DecodeEngine, HTTP front and Optimizer at toy sizes under
    one CPU profiler session: every reader finds what it reads."""
    from bigdl_tpu.obs.metrics import get_registry, set_registry
    before = get_registry()
    set_registry(traced_toy_run["registry"])
    try:
        value = reader(base)({"planes": traced_toy_run["planes"]})
    finally:
        set_registry(before)
    assert isinstance(value, float) and value >= 0.0
    if base == "prefill_pad_share":
        assert 0.0 < value < 100.0
