"""ISSUE 8: ``--strategy`` through the perf harness on the 8-device CPU
mesh — dp loss parity with the single-device run (the reference's
DistriOptimizerSpec bar), mesh/device-count stamping in every JSON
line, and the cli/common strategy machinery (spec parsing, mesh shapes,
the stepsPerDispatch/innerSteps x strategy SystemExit contract the hidden
data_parallel branch used to skip)."""

import jax
import pytest

from bigdl_tpu.cli import common
from bigdl_tpu.cli.perf import run


def test_perf_strategy_dp_matches_single_device():
    """Acceptance: perf --strategy dp on 8 virtual CPU devices lands on
    the single-device loss, with strategy/mesh/n_devices stamped."""
    assert len(jax.devices()) == 8
    single = run("lenet5", 16, 4, "constant", use_bf16=False)
    dp = run("lenet5", 16, 4, "constant", use_bf16=False, strategy="dp")
    assert abs(single["final_loss"] - dp["final_loss"]) < 1e-4
    assert single["strategy"] is None and single["mesh"] is None
    assert single["n_devices"] == 1
    assert dp["strategy"] == "dp"
    assert dp["mesh"] == {"data": 8}
    assert dp["n_devices"] == 8


def test_perf_deprecated_data_parallel_alias():
    out = run("lenet5", 16, 2, "constant", use_bf16=False,
              data_parallel=True)
    assert out["strategy"] == "dp" and out["mesh"] == {"data": 8}


def test_perf_strategy_tp_runs():
    out = run("lenet5", 16, 2, "constant", use_bf16=False, strategy="tp")
    assert out["strategy"] == "tp"
    assert out["mesh"] == {"data": 2, "model": 4}
    assert out["n_devices"] == 8
    import numpy as np
    assert np.isfinite(out["final_loss"])


def test_perf_strategy_tp_sized_axis():
    out = run("lenet5", 16, 2, "constant", use_bf16=False,
              strategy="tp:2")
    assert out["mesh"] == {"data": 4, "model": 2}


def test_perf_strategy_ep_runs():
    out = run("transformer_lm", 8, 1, "random", use_bf16=False,
              strategy="ep", seq_len=16)
    assert out["strategy"] == "ep"
    assert out["mesh"] == {"expert": 8}
    assert out["bn_fused"] == "off"
    import numpy as np
    assert np.isfinite(out["final_loss"])
    assert out["step_gflops_analytic"] > 0  # MoE dots counted


def test_perf_strategy_sp_runs():
    """sp rides jax.shard_map (ring attention): the leg runs and stamps
    its seq mesh."""
    out = run("transformer_lm", 8, 1, "random", use_bf16=False,
              strategy="sp", seq_len=32)
    assert out["mesh"] == {"data": 2, "seq": 4}


def test_perf_strategy_sp_needs_lm():
    with pytest.raises(SystemExit, match="transformer_lm"):
        run("lenet5", 16, 1, "constant", use_bf16=False, strategy="sp")


def test_inner_steps_strategy_contract():
    """The PR 1 validation the hidden data_parallel branch ignored:
    dispatch amortization x multi-device strategy is a clean refusal."""
    with pytest.raises(SystemExit, match="innerSteps"):
        run("lenet5", 16, 2, "constant", use_bf16=False, strategy="dp",
            inner_steps=4)


# ------------------------------------------------ cli/common machinery
def test_parse_strategy_spec():
    assert common.parse_strategy_spec(None) == (None, None)
    assert common.parse_strategy_spec("dp") == ("dp", None)
    assert common.parse_strategy_spec("tp:4") == ("tp", 4)
    with pytest.raises(SystemExit, match="unknown strategy"):
        common.parse_strategy_spec("zp")
    with pytest.raises(SystemExit, match="integer"):
        common.parse_strategy_spec("tp:four")


def test_strategy_mesh_axes_shapes():
    assert common.strategy_mesh_axes("dp", 8) == {"data": 8}
    assert common.strategy_mesh_axes("tp", 8) == {"data": 2, "model": 4}
    assert common.strategy_mesh_axes("sp", 8, 2) == {"data": 4, "seq": 2}
    assert common.strategy_mesh_axes("pp", 8) == {"pipe": 4, "data": 2}
    assert common.strategy_mesh_axes("ep", 8) == {"expert": 8}
    with pytest.raises(SystemExit, match="divide"):
        common.strategy_mesh_axes("tp", 8, 3)


def test_build_strategy_dp_tp_and_guard():
    import argparse

    from bigdl_tpu import nn
    from bigdl_tpu.core import Sequential
    from bigdl_tpu.parallel import DataParallel, TensorParallel

    def args(**kw):
        ns = argparse.Namespace(strategy=None, dataParallel=False,
                                stepsPerDispatch=1)
        for k, v in kw.items():
            setattr(ns, k, v)
        return ns

    assert common.build_strategy(args()) is None
    s = common.build_strategy(args(strategy="dp"))
    assert isinstance(s, DataParallel)
    model = Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    t = common.build_strategy(args(strategy="tp"), model=model)
    assert isinstance(t, TensorParallel)
    with pytest.raises(SystemExit, match="stepsPerDispatch"):
        common.build_strategy(args(strategy="dp", stepsPerDispatch=4))
    with pytest.raises(SystemExit, match="perf"):
        common.build_strategy(args(strategy="ep"))


def test_perf_cli_tta_strategy_guard():
    from bigdl_tpu.cli import perf

    with pytest.raises(SystemExit, match="timeToAcc"):
        perf.main(["-m", "resnet20_cifar", "--timeToAcc", "0.5",
                   "--strategy", "tp", "--platform", "cpu"])
