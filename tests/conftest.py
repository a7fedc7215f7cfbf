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


@pytest.fixture(scope="session")
def traced_toy_run(tmp_path_factory):
    """One CPU profiler session (``benchmark.lib.trace``) over the real
    serving stack and the real training loop at toy sizes: four streamed
    ``/generate`` requests on a two-slot ``DecodeEngine`` (so two of them
    queue), a pause in which the decode loop idles, a fifth request, and
    three ``Optimizer`` steps. Gives the planes, the engine's registry and
    the prompt lengths sent."""
    import threading
    import time

    from benchmark.lib import trace
    from benchmark.lib.serve import stream_generate
    from bigdl_tpu import models, nn
    from bigdl_tpu.dataset import BatchDataSet
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    from bigdl_tpu.serving import (DecodeEngine, MetricsRegistry,
                                   ServingApp, make_server)

    model = models.transformer_lm(50, d_model=32, num_layers=2,
                                  num_heads=2, max_len=64)
    params = model.init(jax.random.PRNGKey(1))
    registry = MetricsRegistry()
    decoder = DecodeEngine(model, params, slots=2, max_waiting=8,
                           metrics=registry)
    app = ServingApp(name="toy", metrics=registry, decoder=decoder)
    srv = make_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    decoder.start()
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    prompts = [5, 9, 17, 33, 7]  # tokens; buckets are powers of two

    def request(n_prompt, max_new=4):
        rec = {"arrivals": [], "out": []}
        stream_generate(port, list(range(1, n_prompt + 1)), max_new, rec,
                        timeout=120)
        assert len(rec["out"]) == max_new, rec

    def optimize():
        x = np.random.RandomState(0).randn(24, 6).astype(np.float32)
        y = (x.sum(axis=1) > 0).astype(np.int32)
        Optimizer(nn.Sequential(nn.Linear(6, 2), nn.LogSoftMax()),
                  BatchDataSet(x, y, 8), nn.ClassNLLCriterion(),
                  optim_method=SGD(learning_rate=0.1),
                  end_when=Trigger.max_iteration(3)).optimize()

    try:
        for n in prompts:  # every program compiles before the session
            request(n)
        optimize()
        before = {k: registry.counter(k).value for k in (
            "prompt_tokens_total", "prefill_bucket_tokens_total",
            "decode_queued_total", "decode_queue_wait_seconds_total")}
        log_dir = str(tmp_path_factory.mktemp("traced_toy"))
        trace.start(log_dir)
        wave = [threading.Thread(target=request, args=(n, 12))
                for n in prompts[:4]]
        for t in wave:
            t.start()
        for t in wave:
            t.join(120)
        time.sleep(0.2)  # the loop idles, then a request wakes it
        request(prompts[4])
        optimize()
        planes = trace.stop_and_load(log_dir)
    finally:
        srv.shutdown()
        srv.server_close()
        app.close()
    return {"planes": planes, "registry": registry, "before": before,
            "buckets": [decoder.prompt_bucket_for(n) for n in prompts],
            "prompts": prompts}
