"""``cache_write_time_share``: on small synthetic planes, the share of busy
time under the decode-step program's ``while`` loops (the scatter the
parent's step holds), under its ``cache_write_rows`` kernel calls (this
program's step), under neither, and nothing from a trace with no busy
time; a ``while`` of the prefill program is not counted."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import trace  # noqa: E402

spec = importlib.util.spec_from_file_location(
    "benchmark_metric_cache_write_time_share", os.path.join(
        ROOT, "benchmark", "metrics", "cache_write_time_share.py"))
share = importlib.util.module_from_spec(spec)
spec.loader.exec_module(share)

MS = 1_000_000  # ns
CONFIG = {"serve": {"programs": {"decode_step": "jit__one",
                                 "prefill": "jit__prefill"}}}
SCATTER = [  # a step of 20 ms: two loops of 4 ms that cover their bodies
    ("fusion/kOutput bf16[48,1,1,3072]", 0, 6 * MS),
    ("while bf16[48,1,2,4096,128]", 6 * MS, 4 * MS),
    ("dynamic-update-slice bf16[48,1,2,4096,128]", 7 * MS, 1 * MS),
    ("broadcast_select_fusion/kLoop bf16[1,1,2,1,128]", 8 * MS, 1 * MS),
    ("while bf16[48,1,2,4096,128]", 10 * MS, 4 * MS),
    ("fusion/kOutput f32[48,2,12,4096]", 14 * MS, 6 * MS)]
KERNEL = [
    ("fusion/kOutput bf16[48,1,1,3072]", 0, 6 * MS),
    ("cache_write_rows bf16[48,2,4096,128]", 6 * MS, 1 * MS),
    ("cache_write_rows bf16[48,2,4096,128]", 7 * MS, 1 * MS),
    ("fusion/kOutput f32[48,2,12,4096]", 8 * MS, 12 * MS)]
NEITHER = [("fusion/kOutput bf16[48,1,1,3072]", 0, 20 * MS)]
PREFILL = [  # 20 ms of prefill after the step, with a scan's loop in it
    ("while f32[1,5120,16]", 22 * MS, 10 * MS),
    ("flash_fwd bf16[20,256,128]", 32 * MS, 10 * MS)]


def run_of(step_ops, prefill=False):
    ops = step_ops + (PREFILL if prefill else [])
    modules = [("jit__one(7)", 0, 20 * MS)]
    if prefill:
        modules.append(("jit__prefill(9)", 22 * MS, 20 * MS))
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": trace.OP_LINE, "events": ops},
        {"name": trace.MODULE_LINE, "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [("thread main", 0, 50 * MS)]}]}]
    return {"planes": planes, "reduced": trace.reduce(planes),
            "config": CONFIG}


@pytest.mark.parametrize("ops,prefill,want", [
    (SCATTER, False, 100 * 8 / 20),
    (KERNEL, False, 100 * 2 / 20),
    (NEITHER, False, 0.0),
    (SCATTER, True, 100 * 8 / 40),  # the prefill's own loop is not a write
    (KERNEL, True, 100 * 2 / 40),
], ids=["scatter", "kernel", "neither", "scatter_beside_a_prefill",
        "kernel_beside_a_prefill"])
def test_share_of_busy_time(ops, prefill, want):
    run = run_of(ops, prefill)
    assert run["reduced"]["busy_s"] == pytest.approx(0.040 if prefill
                                                     else 0.020)
    assert share.read(run) == pytest.approx(want)


def test_nothing_without_a_trace_or_busy_time():
    run = run_of(SCATTER)
    assert share.read(dict(run, reduced=None)) is None
    assert share.read(dict(run, reduced=dict(run["reduced"],
                                             busy_s=0.0))) is None


def test_a_step_of_another_name_is_not_read():
    run = run_of(SCATTER)
    run["config"] = {"serve": {"programs": {"decode_step": "jit_step"}}}
    assert share.read(run) == 0.0
