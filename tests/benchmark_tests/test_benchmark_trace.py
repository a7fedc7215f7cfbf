"""The reduction from a trace to numbers, on a small synthetic plane:
union of intervals (two overlapping ops and a nested step line, which the
sum over every line in ``obs/attrib.py`` would count twice), kernel time
by name, exposed collective time, idle gaps named by the host's span."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import trace  # noqa: E402

MS = 1_000_000  # ns


def plane(name, **lines):
    return {"name": name, "lines": [
        {"name": ln.replace("_", " "), "events": evs}
        for ln, evs in lines.items()]}


def synthetic():
    ops = [
        ("fusion.1", 0 * MS, 10 * MS),
        ("flash_fwd", 10 * MS, 20 * MS),
        ("fusion.2", 25 * MS, 10 * MS),       # overlaps flash_fwd by 5 ms
        ("all-reduce.1", 40 * MS, 20 * MS),   # 5 ms idle before it
        ("fusion.3", 50 * MS, 5 * MS),        # hides 5 ms of the collective
        ("flash_dq", 80 * MS, 20 * MS),       # 20 ms idle before it
    ]
    device = plane("/device:TPU:0",
                   XLA_Ops=ops,
                   XLA_Modules=[("jit_train_step(123)", 0, 60 * MS),
                                ("jit_train_step(123)", 80 * MS, 20 * MS),
                                ("jit__prefill(9)", 60 * MS, 1 * MS)],
                   Steps=[("step 0", 0, 100 * MS)])  # nests everything
    host = plane("/host:CPU", python=[
        ("bench:optimizer_step", 58 * MS, 30 * MS),
        ("PjitFunction(_prefill)", 36 * MS, 2 * MS),
        ("thread main", 0, 100 * MS)])
    return [device, host]


@pytest.mark.parametrize("text,want", [
    ("%fusion.359 = (f32[3072]{0:T(1024)}, bf16[2,4096,3072]{2,1,0:T(8,128)"
     "(2,1)}) fusion(bf16[2,4096,3072]{2,1,0} %get-tuple-element.462), "
     "kind=kOutput, calls=%fused_computation.479",
     "fusion/kOutput bf16[2,4096,3072]"),
    ("%flash_dkv.8 = (bf16[48,4096,128]{2,1,0:T(8,128)(2,1)}, bf16[48,4096,"
     "128]{2,1,0}) custom-call(bf16[48,4096,128]{2,1,0} %bitcast.1160)",
     "flash_dkv bf16[48,4096,128]"),
    ("%all-reduce-done.3 = f32[3072,12288]{1,0} all-reduce-done(%x)",
     "all-reduce-done f32[3072,12288]"),
    ("%copy.1 = f32[]{:T(128)} copy(f32[] %p)", "copy f32[]"),
    ("fusion.1", "fusion.1"),
])
def test_op_label_shortens_the_hlo_text(text, want):
    assert trace.op_label(text) == want


def test_union_and_overlap():
    assert trace.union([(5, 9), (0, 3), (2, 4), (6, 7)]) == [(0, 4), (5, 9)]
    assert trace.total([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace.overlap([], [(0, 1)]) == 0


def test_busy_is_a_union_not_a_sum():
    r = trace.reduce(synthetic())
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    # ops sum to 85 ms, and the step and module lines would add 181 more;
    # the union of the op line is 0-35, 40-60, 80-100 = 75 ms
    assert sum(r["op_seconds"].values()) == pytest.approx(0.085)
    assert r["busy_s"] == pytest.approx(0.075)


def test_kernel_time_by_name_and_programs():
    r = trace.reduce(synthetic())
    assert trace.kernel_seconds(r, ("flash_fwd", "flash_dq")) == \
        pytest.approx(0.040)
    assert trace.kernel_seconds(r, ("flash_dkv",)) == 0
    assert trace.module_stats(r, "jit_train_step") == (2, pytest.approx(0.08))
    assert trace.module_stats(r, "jit__prefill") == (1, pytest.approx(0.001))
    assert trace.module_stats(r, "jit__one") == (0, 0)


def test_exposed_collective_time():
    # the all-reduce runs 40-60; fusion.3 computes during 50-55
    assert trace.reduce(synthetic())["collective_exposed_s"] == \
        pytest.approx(0.015)


def test_idle_gaps_are_named_by_the_host_span():
    r = trace.reduce(synthetic())
    gaps = dict(r["idle_gaps"])
    # the innermost host span over the gap's middle names it: 60-80 lies
    # under bench:optimizer_step, 35-40 under jax's own PjitFunction span
    assert gaps == {"optimizer_step": pytest.approx(0.020),
                    "PjitFunction(_prefill)": pytest.approx(0.005)}
    assert dict(trace.idle_gaps(synthetic(), [(0, 60 * MS)], 0, 100 * MS,
                                named=0)) == {
        "gaps_beyond_the_0_longest": pytest.approx(0.040)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] in ("flash_fwd", "flash_dq", "all-reduce.1")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_two_devices_are_averaged_and_no_device_is_nothing():
    two = synthetic()
    two.append(plane("/device:TPU:1", XLA_Ops=[("fusion.1", 0, 100 * MS)]))
    r = trace.reduce(two)
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx((0.075 + 0.100) / 2)
    assert trace.reduce([synthetic()[1]]) is None
