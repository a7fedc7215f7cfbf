"""``benchmark/run.py --rehearse-cpu``: the real control flow at the files'
toy sizes on the CPU, one train cell and one serve cell; and the refusal to
run, with nothing on stdout, where there is no chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(*args):
    # one compute thread: the suite's timing tests run beside this child
    env = dict(os.environ, XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("cell,trace", [("train_4k", "0"),
                                        ("serve_code_batch", "1")])
def test_rehearsal_prints_the_contract_line(cell, trace):
    p = run_cell("--workload", cell, "--seed", str(2 ** 31 + 7),
                 "--seconds", "2", "--trace", trace, "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in line["device"]
    # no CPU number under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    parts = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith('{"info": "setup_parts"')]
    assert parts and parts[0]["setup_s"] > 0


def test_without_a_chip_it_fails_with_empty_stdout():
    p = run_cell("--workload", "train_4k", "--seed", "1", "--seconds", "2",
                 "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
