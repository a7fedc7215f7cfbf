"""Test harness: force an 8-device CPU platform so multi-chip sharding logic
is exercised without TPU hardware — the analog of the reference testing
multi-node logic on local-mode Spark (SURVEY.md §4: Engine.init(4,4,true) +
SparkContext("local[1]")).

CPU is selected via ``jax.config.update('jax_platforms', 'cpu')`` so the
suite runs on the CPU whether or not ``JAX_PLATFORMS=cpu`` is also in the
environment (the tier-1 command sets it; a bare ``pytest`` need not).
"""

import os
import sys

# Must be in the environment before the first backend initialization.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# BIGDL_TPU_TESTS=1 keeps the real backend so @pytest.mark.tpu tests (the
# compiled Pallas path) can run in the bench environment:
#   BIGDL_TPU_TESTS=1 python -m pytest tests/ -m tpu
if not os.environ.get("BIGDL_TPU_TESTS"):
    jax.config.update("jax_platforms", "cpu")

import re  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The suite may skip ONLY for a missing runtime dependency: the real TPU
# backend, pytorch (golden-test oracle), or the native C++ library/libjpeg.
# Any other skip reason is turned into a test failure so coverage cannot
# silently shrink (VERDICT r4 item 8; the reference gates explicitly too,
# torch/TH.scala:36-40).
_ALLOWED_SKIP = re.compile(
    r"TPU backend|torch|native lib|libjpeg", re.IGNORECASE)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.skipped and not hasattr(report, "wasxfail"):
        lr = report.longrepr
        reason = lr[2] if isinstance(lr, tuple) else str(lr)
        if not _ALLOWED_SKIP.search(reason):
            report.outcome = "failed"
            report.longrepr = (
                f"disallowed skip reason {reason!r} — the suite may only "
                "skip for a missing TPU backend, torch, or the native "
                "library (tests/conftest.py)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: needs a real TPU backend (compiled Pallas path); skipped on "
        "the CPU test platform, run manually in the bench environment")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 sweep (-m 'not slow'); run by "
        "dedicated CI jobs (chaos-smoke) or manually")


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _np_seed():
    np.random.seed(0)
