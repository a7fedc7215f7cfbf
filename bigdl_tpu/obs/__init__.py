"""Unified observability layer (ISSUE 7).

BigDL's observability story — per-module wall-time counters
(``AbstractModule.getTimes``) + cluster-wide named counters aggregated
through Spark accumulators (``optim/Metrics.scala``, paper §4) — was
reproduced in fragments: hand-rolled ``time.time()`` deltas in the
Optimizer, a serving-only metrics registry, an offline-only xplane
reader. This package is the substrate built once:

* :mod:`spans`   — structured step-phase tracing: ``span("data_wait")``
  around the real phases of training and serving: a profiler annotation
  (``bigdl:data_wait`` in any open ``jax.profiler`` session, on the device
  trace's clock) and, under ``--obs``, a thread-safe ring with
  Chrome-trace/Perfetto export;
* :mod:`metrics` — the shared process-global registry
  (Counter/Gauge/Histogram + Prometheus exposition + provenance
  stamping), promoted from ``serving/metrics.py`` and now fed by
  training (step-phase histograms), resilience (fault/retry counters),
  and serving alike;
* :mod:`capture` — on-demand ``jax.profiler`` windows mid-run
  (``--traceSteps N@M``, SIGUSR2, touch-file). A capture is verified,
  not attributed: on close the ``*.xplane.pb`` must parse with
  ``utils/xplane`` and the record says where it is. Reading it is
  XProf/Perfetto's job for an operator; the one reducer whose numbers
  the ledger holds is ``benchmark/lib/trace.py``;
* :mod:`http`    — a live ``/metrics`` listener for training runs,
  reusing serving's exposition format.

Wired as ``--obs``/``--traceDir``/``--traceSteps``/``--metricsPort`` on
the perf + training CLIs (``cli/common.py``), with per-step phase
columns (``data_wait_s``, ``h2d_s``, ``dispatch_s``, ``device_s``,
``ckpt_s``, ``stall_frac``) stamped into every perf JSON line next to
``bn_fused``/``lint``/``supervisor``.
"""

from bigdl_tpu.obs import memory
from bigdl_tpu.obs.capture import (CaptureController, parse_trace_steps,
                                   TOUCH_FILE_NAME)
from bigdl_tpu.obs.http import MetricsServer, start_metrics_server
from bigdl_tpu.obs.memory import (HbmSampler, build_plan,
                                  device_hbm_bytes, forecast, handle_oom,
                                  is_resource_exhausted, plan_for_model,
                                  tree_bytes, write_oom_report)
from bigdl_tpu.obs.metrics import (Counter, DEFAULT_LATENCY_BUCKETS_MS,
                                   Gauge, Histogram, MetricsRegistry,
                                   PHASE_BUCKETS_MS, TRAIN_PHASES,
                                   get_registry, phase_histograms,
                                   reset_registry, set_registry)
from bigdl_tpu.obs.spans import (Tracer, counter, disable, enable,
                                 enabled, get_tracer, instant, set_tracer,
                                 span)

__all__ = [
    "CaptureController", "parse_trace_steps", "TOUCH_FILE_NAME",
    "MetricsServer", "start_metrics_server",
    "memory", "HbmSampler", "build_plan", "device_hbm_bytes", "forecast",
    "handle_oom", "is_resource_exhausted", "plan_for_model", "tree_bytes",
    "write_oom_report",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS_MS", "PHASE_BUCKETS_MS", "TRAIN_PHASES",
    "get_registry", "phase_histograms", "reset_registry", "set_registry",
    "Tracer", "counter", "disable", "enable", "enabled", "get_tracer",
    "instant", "set_tracer", "span",
]
