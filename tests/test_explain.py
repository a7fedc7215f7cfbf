"""ISSUE 32: what is left of ``bigdl-tpu explain`` and of a capture once
the program's own trace reducer is gone.

``explain <model>`` prints the HBM plan (table and ``--json``), ``explain
<directory>`` exits 2 and says where a capture is read, and a capture
taken through ``perf``'s own ``--traceSteps`` is a plain ``jax.profiler``
directory that holds the program's spans: verified, not attributed."""

import json

from bigdl_tpu import obs
from bigdl_tpu.cli import main as cli_main


def test_explain_model_prints_the_plan(capsys):
    """The table and the --json line carry the same plan: its total and
    the predicted maximum batch."""
    from bigdl_tpu.cli import explain
    from bigdl_tpu.obs.memory import _fmt_bytes

    assert explain.main(["lenet5", "-b", "8", "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "lenet5" and out["batch"] == 8
    assert out["total_bytes"] > 0 and out["categories"]["params"] > 0
    assert out["plan_2x"]["total_bytes"] > out["total_bytes"]
    max_batch = out["forecast"]["predicted_max_batch"]
    assert max_batch > 16

    assert explain.main(["lenet5", "-b", "8"]) == 0
    table = capsys.readouterr().out
    assert table.startswith("memory plan: lenet5 b=8")
    assert _fmt_bytes(out["total_bytes"]) in table
    assert f"predicted max batch {max_batch}" in table


def test_explain_directory_exits_2_and_says_where_a_capture_is_read(
        tmp_path, capsys):
    assert cli_main.main(["explain", str(tmp_path)]) == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert len(cap.err.strip().splitlines()) == 1
    for word in ("jax.profiler", "XProf", "Perfetto",
                 "benchmark/lib/trace.py"):
        assert word in cap.err


def test_perf_capture_holds_the_programs_spans_and_no_attribution(
        tmp_path, capsys):
    """``perf --traceSteps 2@1`` through the Optimizer: the capture
    record says where the directory is and that it parsed, nothing
    more; the directory opens with jax's own reader and holds
    ``bigdl:train_step`` with ``bigdl:loss_fetch`` inside it."""
    import jax

    from bigdl_tpu.cli import perf

    obs.disable()
    obs.reset_registry()
    try:
        perf.main(["-m", "resnet20_cifar", "-b", "16", "--f32",
                   "--timeToAcc", "0.99", "--maxEpoch", "1",
                   "--imageSize", "32", "--classes", "4",
                   "--trainPerClass", "16", "--valPerClass", "4",
                   "--traceDir", str(tmp_path / "tr"),
                   "--traceSteps", "2@1"])
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        out = json.loads(lines[-1])
        series = obs.get_registry().render()
    finally:
        obs.disable()
        obs.reset_registry()

    (rec,) = out["obs"]["captures"]
    assert rec["ok"] and rec["trigger"] == "traceSteps:2@1"
    assert (rec["start_step"], rec["stop_step"]) == (1, 3)
    for gone in ("attrib", "attrib_error", "grad_comm"):
        assert gone not in rec
    for gone in ("attrib", "collective_s", "collective_frac"):
        assert gone not in out
    assert not [ln for ln in series.splitlines()
                if ln.startswith("attrib_")]

    from bigdl_tpu.utils.xplane import find_xplane_pb
    data = jax.profiler.ProfileData.from_file(find_xplane_pb(rec["dir"]))
    spans = {"bigdl:train_step": [], "bigdl:loss_fetch": []}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append((line.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    steps, fetches = spans.values()
    # the session opens inside step 1 (the profiler keeps a span only if
    # it began and ended inside the session), so step 2 is the whole one
    assert steps and fetches
    for ln, s, e in steps:  # every whole step holds its fetch, same thread
        assert any(l2 == ln and s <= s2 and e2 <= e
                   for l2, s2, e2 in fetches)
