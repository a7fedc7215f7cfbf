"""ISSUE 21 bring-up contracts that can be checked off the chip, in
seconds: where the compile cache lives, what an unknown device does to a
utilization number, and how ``chip_smoke.py`` behaves with no chip."""

import ast
import os
import subprocess
import sys
import time

import jax
import pytest

from bigdl_tpu.cli import common, perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ------------------------------------------------------------ compile cache
def test_cache_dir_left_to_jax_when_variable_set(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert common.compile_cache_dir() is None
    before = jax.config.jax_compilation_cache_dir
    common.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_one_fixed_path_in_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    here = common.compile_cache_dir()
    assert here == common.compile_cache_dir()
    assert here == os.path.join(REPO, ".jax_cache")
    # another process, another cwd, another pid, another HOME: same path
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from bigdl_tpu.cli.common import compile_cache_dir as d; "
         "print(d())"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert out.stdout.strip() == here, out.stderr[-500:]


# --------------------------------------------------------- device peak table
class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_unknown_accelerator_kind_raises():
    assert perf._peak_flops(_Dev("tpu", "TPU v5 lite")) == (197e12, "v5lite")
    with pytest.raises(ValueError, match="QuantumChip 9000"):
        perf._peak_flops(_Dev("tpu", "QuantumChip 9000"))


def test_cpu_platform_has_no_mfu():
    assert perf._peak_flops(jax.devices()[0]) == (None, "cpu")
    out = perf.run("lenet5", 2, 1, "random", use_bf16=False)
    assert out["mfu"] is None and out["mfu_pct"] is None
    assert out["peak_flops_assumed"] is None
    assert out["mosaic_kernels"] == []  # interpreted kernels are plain HLO


# ------------------------------------------------------------ chip_smoke.py
def _imports(path):
    """Top-level module names a file imports anywhere outside the
    functions run only in its child process."""
    tree = ast.parse(open(path).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "leg_train":
            node.body = []  # the train CHILD imports jax; the parent must not
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    return names


def test_smoke_parent_imports_neither_jax_nor_bigdl_tpu():
    assert not {"jax", "jaxlib", "numpy", "bigdl_tpu"} & _imports(SMOKE)


def test_smoke_without_a_chip_fails_fast_and_prints_no_result():
    t0 = time.time()
    out = subprocess.run([sys.executable, SMOKE], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert time.time() - t0 < 60
    assert out.stdout.strip() == ""  # no rate, no "ok", nothing
    assert "tpu" in out.stderr.lower()


def test_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "bigdl_tpu" in out.stderr
