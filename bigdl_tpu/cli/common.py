"""Shared CLI wiring (reference models/*/Utils.scala scopt parsers +
models/inception/Options.scala — one typed flag surface instead of the
reference's env-var / system-property / scopt triple, SURVEY.md §5
"Config / flag system")."""

from __future__ import annotations

import argparse
import json
import logging
import os
import time
from typing import Optional, Sequence

import numpy as np


def _add_platform_arg(p: argparse.ArgumentParser) -> None:
    p.add_argument("--platform", default=None,
                   choices=["cpu", "tpu"],
                   help="force the jax backend before first device use "
                        "(--platform cpu runs the CLI off-chip; --platform "
                        "tpu makes a missing chip an error instead of a "
                        "silent CPU run)")


def add_autotune_arg(p: argparse.ArgumentParser) -> None:
    p.add_argument("--autotune", default="off",
                   choices=["off", "cached", "measure"],
                   help="per-shape kernel autotuner (bigdl_tpu.tuning): "
                        "conv pass layouts, flash-attention block sizes, "
                        "BN stats row block. 'cached' = read persisted "
                        "decisions (~/.cache/bigdl_tpu/autotune/"
                        "<device>.json), never measure; 'measure' = time "
                        "candidates on cache miss and persist the winner "
                        "(off-TPU this dry-records the defaults without "
                        "timing); 'off' = shipped defaults")


def add_fused_bn_arg(p: argparse.ArgumentParser) -> None:
    """--fusedBN [off|stats|apply]: Pallas BN for training-mode batch
    norm. Bare ``--fusedBN`` keeps the historical meaning (the stats
    kernel) so existing invocations/scripts are unchanged."""
    p.add_argument("--fusedBN", nargs="?", const="stats", default=None,
                   choices=["off", "stats", "apply"],
                   help="Pallas BN path (ops/bn_kernel.py; single-device "
                        "jit, auto-disabled under SPMD): 'stats' = "
                        "single-read stats kernel (measured −46%% on "
                        "chip, PERF.md §8.2 — kept for A/Bs); 'apply' = "
                        "the FULL fused block: stats+apply+absorbed-ReLU "
                        "in one kernel forward, Σdy/Σ(dy·x̂)+dx in one "
                        "kernel backward (PERF.md §10). Bare --fusedBN "
                        "means 'stats' (historical)")


def add_lint_arg(p: argparse.ArgumentParser) -> None:
    """--lint[=strict]: tpulint pre-flight (bigdl_tpu.analysis) before
    the run compiles anything — trace-time rule evaluation on CPU in
    seconds. ``strict`` refuses to launch on error-severity findings."""
    p.add_argument("--lint", nargs="?", const="on", default=None,
                   choices=["on", "strict"],
                   help="pre-flight static analysis of the model/config "
                        "(bigdl_tpu.analysis, PERF.md §12): dtype "
                        "upcasts, donation, Pallas tiling/VMEM, fusion "
                        "opportunities (unfused BN, GEMM-eligible "
                        "convs), host syncs. Bare --lint prints the "
                        "report and continues; --lint=strict exits "
                        "nonzero on error-severity findings. Findings "
                        "are stamped into perf JSON lines as 'lint'")


def add_resilience_args(p: argparse.ArgumentParser) -> None:
    """--supervise/--faultPlan (ISSUE 6): supervised recovery + the
    deterministic fault injector, shared by the training CLIs, perf,
    and serve."""
    p.add_argument("--supervise", nargs="?", const=5, type=int,
                   default=None, metavar="BUDGET",
                   help="supervised recovery (bigdl_tpu.resilience): "
                        "catch retryable faults (transient dispatch "
                        "errors, checkpoint I/O failures, checksum "
                        "mismatches, soft preemptions), retry with "
                        "exponential backoff + deterministic jitter, "
                        "auto-resume from the newest checksum-VALID "
                        "checkpoint, give up past BUDGET retries (bare "
                        "flag = 5). Fault-free overhead is one pointer "
                        "check per step")
    p.add_argument("--faultPlan", default=None, metavar="SPEC|FILE",
                   help="deterministic seeded fault injection "
                        "(bigdl_tpu.resilience.faults): ';'-separated "
                        "kind@site:VISITS[:ARG] entries or a JSON file — "
                        "e.g. 'preempt@step:7' (process-fatal kill "
                        "before step 7), 'dispatch@step:p0.01;seed=3' "
                        "(1%% transient step failures), "
                        "'corrupt@ckpt_save:2' (bit-rot the 2nd "
                        "checkpoint), 'stall@step:4:0.25', "
                        "'kill_device@step:5:1' (lose 1 device before "
                        "step 5 — recoverable only under --elastic). "
                        "Sites: data, step, ckpt_save, ckpt_restore, "
                        "infer, request. No-op when unset")
    p.add_argument("--elastic", default=None, choices=["hold", "scale"],
                   metavar="POLICY",
                   help="elastic data-parallelism "
                        "(bigdl_tpu.resilience.elastic): on device loss "
                        "(kill_device fault / DeviceLossFault) re-form "
                        "the mesh at the surviving count, re-resolve the "
                        "grad-comm bucket bound for the new n_devices, "
                        "and resume from the last valid checkpoint — "
                        "holding the global batch (hold: pad per-device "
                        "batches) or scaling it (scale: trim to "
                        "divisibility). dp strategy only")
    p.add_argument("--minDevices", type=int, default=1, metavar="N",
                   help="give up cleanly (SupervisorGaveUp) when fewer "
                        "than N healthy devices survive — elastic "
                        "reshape never thrashes below a viable mesh "
                        "(default 1)")


def add_obs_args(p: argparse.ArgumentParser) -> None:
    """--obs/--traceDir/--traceSteps/--metricsPort (ISSUE 7): the
    unified observability layer, shared by perf and every training
    CLI."""
    p.add_argument("--obs", action="store_true",
                   help="step-phase observability (bigdl_tpu.obs): span "
                        "tracing around the loop's real phases "
                        "(data_wait/h2d/dispatch/device/ckpt), per-step "
                        "phase histograms in the shared metrics "
                        "registry, and phase columns stamped into perf "
                        "JSON lines. Off: zero-cost no-ops, output "
                        "byte-identical modulo null columns")
    p.add_argument("--traceDir", default=None, metavar="DIR",
                   help="observability artifact dir: the Chrome-trace "
                        "span timeline (spans.trace.json — load in "
                        "chrome://tracing or ui.perfetto.dev) plus any "
                        "on-demand profile capture windows. Implies "
                        "--obs")
    p.add_argument("--traceSteps", default=None, metavar="N@M",
                   help="capture a jax.profiler trace of steps M..M+N-1 "
                        "mid-run into --traceDir/capture_<M>. On close "
                        "the capture is verified (its .xplane.pb parses "
                        "with utils/xplane) and its record says where "
                        "it is; it is not reduced to numbers here: open "
                        "it in XProf or Perfetto (the program's spans "
                        "are in it as bigdl:<name>). Independently, "
                        "SIGUSR2 or `touch DIR/CAPTURE` opens a bounded "
                        "window on a run already in flight")
    p.add_argument("--metricsPort", type=int, default=None, metavar="PORT",
                   help="start a live /metrics listener (serving's "
                        "Prometheus exposition format) for this "
                        "training/perf run; 0 = auto-pick a free port "
                        "(printed and stamped into the perf JSON obs "
                        "annotation). An explicit port that is already "
                        "taken is a clean SystemExit, not a mid-run "
                        "socket traceback")


# the --dataWorkers/--prefetchDepth/--stage surface (ISSUE 13): the
# async input-pipeline executor + host->device staging, shared by perf
# and every training CLI (must mirror dataset.pipeline.STAGE_CHOICES —
# asserted in tests, not imported here, so argparse setup never pulls
# the jax-importing dataset package)
PIPELINE_STAGE_CHOICES = ("off", "host", "device")


def add_pipeline_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--dataWorkers", type=int, default=0, metavar="N",
                   help="async input-pipeline executor "
                        "(bigdl_tpu.dataset.pipeline, the reference's "
                        "MTLabeledBGRImgToBatch model): N decode/augment "
                        "worker threads race the epoch plan's sample "
                        "tickets and reassemble batches in submission "
                        "order — the batch stream is bit-identical for "
                        "ANY worker count and under kill+resume "
                        "(per-sample (seed, epoch, index) rngs). 0 = "
                        "legacy single-threaded feed")
    p.add_argument("--prefetchDepth", type=int, default=2, metavar="D",
                   help="max batches prepared ahead of the consumer — "
                        "bounds both the executor's in-flight batch "
                        "reassembly (workers block past it) and the "
                        "staging queue (default 2: double buffering)")
    p.add_argument("--stage", default="off",
                   choices=list(PIPELINE_STAGE_CHOICES),
                   help="host->device staging thread: 'host' prepares "
                        "assembled batches ahead; 'device' additionally "
                        "jax.device_put's batch N+1 — committed to the "
                        "--strategy sharded layout — while the device "
                        "runs step N, so dispatch stops paying the h2d "
                        "copy; 'off' = feed inline (default)")


def build_feed(dataset, args, strategy=None):
    """Wrap a training DataSet in the async pipeline stack per
    ``(--dataWorkers, --prefetchDepth, --stage)``. Returns
    ``(dataset, provenance|None)`` — provenance is what perf stamps as
    the ``pipeline`` JSON column (also stashed on ``args._pipeline``)."""
    workers = int(getattr(args, "dataWorkers", 0) or 0)
    depth = int(getattr(args, "prefetchDepth", 2) or 2)
    stage = getattr(args, "stage", None) or "off"
    if workers <= 0 and stage == "off":
        args._pipeline = None
        return dataset, None
    if (stage == "device"
            and int(getattr(args, "stepsPerDispatch", 1) or 1) > 1):
        # the K-step chunk path restacks its K batches host-side, which
        # would immediately undo (and pay for) the device commit
        logging.getLogger(__name__).warning(
            "--stage device assumes one batch per dispatch; "
            "--stepsPerDispatch > 1 restacks batches host-side — "
            "downgrading to --stage host")
        stage = "host"
    from bigdl_tpu.dataset.pipeline import wrap_pipeline
    ds, prov = wrap_pipeline(dataset, workers=workers, depth=depth,
                             stage=stage, strategy=strategy,
                             seed=getattr(args, "seed", 0))
    args._pipeline = prov
    return ds, prov


class ObsState:
    """What install_observability wired up for this process: whether
    span tracing is on, the capture controller (--traceSteps/SIGUSR2/
    touch-file), the live metrics listener, and where artifacts land.
    ``finalize()`` is idempotent — the perf harness calls it before
    stamping its JSON line, the training path after optimize()."""

    def __init__(self, enabled: bool, trace_dir: Optional[str],
                 capture, server):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.capture = capture
        self.server = server
        # HBM attribution context (ISSUE 12): the harness installs its
        # static memory plan post-compile and a live sampler; the perf
        # JSON mem columns read from here
        self.mem_plan: Optional[dict] = None
        self.mem_sampler = None
        self._final: Optional[dict] = None

    def finalize(self) -> dict:
        """Close any open capture window and export the span timeline;
        returns ``{trace_json, span_events, captures}`` (present keys
        only)."""
        if self._final is not None:
            return self._final
        from bigdl_tpu import obs
        info: dict = {}
        if self.capture is not None:
            self.capture.finish()
            ann = self.capture.annotation()
            if ann:
                info["captures"] = ann
        tracer = obs.get_tracer()
        if tracer is not None and self.trace_dir:
            path = os.path.join(self.trace_dir, "spans.trace.json")
            try:
                n = tracer.export_chrome_trace(path)
            except OSError as e:
                logging.getLogger(__name__).warning(
                    "obs: span export to %s failed: %s", path, e)
            else:
                info["trace_json"] = path
                info["span_events"] = n
                print(f"obs: wrote {n} span(s) to {path}", flush=True)
        if self.server is not None:
            # the bound (possibly auto-picked) port rides in the obs
            # annotation so a log reader can find the scrape endpoint
            info["metrics_port"] = self.server.port
            info["metrics_url"] = self.server.url
        self._final = info
        return info


def install_observability(args) -> Optional[ObsState]:
    """Activate the --obs/--traceDir/--traceSteps/--metricsPort surface
    (no-op returning None when none are set). --traceDir implies span
    tracing; --traceSteps needs --traceDir (captures need a home). The
    state is also stashed on ``args`` for downstream wiring."""
    obs_flag = getattr(args, "obs", False)
    trace_dir = getattr(args, "traceDir", None)
    trace_steps = getattr(args, "traceSteps", None)
    port = getattr(args, "metricsPort", None)
    if not (obs_flag or trace_dir or trace_steps or port is not None):
        return None
    if trace_steps and not trace_dir:
        raise SystemExit("--traceSteps needs --traceDir DIR (somewhere "
                         "for the capture windows to land)")
    from bigdl_tpu import obs
    enabled = bool(obs_flag or trace_dir)
    if enabled and not obs.enabled():
        obs.enable()
    capture = None
    if trace_dir:
        try:
            capture = obs.CaptureController(trace_dir,
                                            trace_steps=trace_steps)
        except ValueError as e:
            raise SystemExit(str(e))
        # arm the OOM post-mortem (ISSUE 12): a RESOURCE_EXHAUSTED
        # anywhere in this process now has a home for its MemoryReport
        obs.memory.install(trace_dir=trace_dir)
    server = None
    if port is not None:
        # an explicit port the user asked for must bind or exit cleanly;
        # 0 auto-picks (the MetricsServer resolves the ephemeral port)
        server = obs.start_metrics_server(obs.get_registry(), port=port,
                                          strict=(port != 0))
    state = ObsState(enabled, trace_dir, capture, server)
    args._obs = state
    return state


def install_fault_plan(args) -> None:
    """Activate --faultPlan process-wide (BIGDL_FAULT_LOG names a JSONL
    file every fired fault is appended to — written before process-fatal
    kinds act, so chaos harnesses can audit post-mortem)."""
    spec = getattr(args, "faultPlan", None)
    if not spec:
        return
    from bigdl_tpu.resilience.faults import install_plan, parse_plan
    try:
        plan = parse_plan(spec)
    except ValueError as e:
        raise SystemExit(f"--faultPlan: {e}")
    install_plan(plan, log_path=os.environ.get("BIGDL_FAULT_LOG"))
    logging.getLogger(__name__).info("fault plan installed: %r", plan)


def run_optimize(make_optimizer, args):
    """``optimize()`` with optional supervision (--supervise): each
    retry builds a FRESH Optimizer (the failed one may hold torn state)
    and resumes from the newest checksum-valid snapshot in
    --checkpoint, replaying the exact rng/batch stream of an
    uninterrupted run (the PR 2 step-equivalence contract)."""
    obs_state = getattr(args, "_obs", None)

    def _make():
        opt = make_optimizer()
        if obs_state is not None and obs_state.capture is not None:
            opt.set_capture(obs_state.capture)
        return opt

    budget = getattr(args, "supervise", None)
    elastic = getattr(args, "elastic", None)
    if budget is None and elastic is None:
        try:
            return _make().optimize()
        finally:
            if obs_state is not None:
                obs_state.finalize()
    from bigdl_tpu.resilience.supervisor import RetryPolicy, Supervisor
    ckpt_dir = getattr(args, "checkpoint", None)
    policy = RetryPolicy(budget=int(budget if budget is not None else 5),
                         seed=getattr(args, "seed", 0))
    if elastic is not None:
        # device loss becomes retryable: each retry's make_optimizer()
        # re-probes healthy_devices() through build_strategy, so the
        # fresh Optimizer is born on the surviving-count mesh with its
        # grad-comm bucket bound re-resolved for the new n_devices
        from bigdl_tpu.resilience.elastic import ElasticSupervisor
        sup = ElasticSupervisor(policy, batch_policy=elastic,
                                min_devices=getattr(args, "minDevices", 1))
    else:
        sup = Supervisor(policy)

    def attempt(n):
        t0 = time.perf_counter()
        if elastic is not None:
            sup.probe()  # SupervisorGaveUp below --minDevices
        opt = _make()
        if n > 0 and ckpt_dir:
            # resume() is a no-op on an empty dir, picks the newest
            # checksum-valid pair otherwise, and falls back to a
            # model-only blob when the kill landed mid-checkpoint (its
            # orphan allowance lets the retry overwrite torn names)
            opt.resume(ckpt_dir)
        if elastic is not None:
            strat = getattr(opt, "strategy", None)
            mesh = getattr(strat, "mesh", None)
            if mesh is not None:
                n_dev = int(mesh.devices.size)
            else:
                import jax
                n_dev = len(jax.devices())
            sup.observe_topology(
                n_dev, restore_ms=((time.perf_counter() - t0) * 1000.0
                                   if n > 0 else None))
        return opt.optimize()

    try:
        result = sup.run(attempt)
    finally:
        if obs_state is not None:
            obs_state.finalize()
    ann = sup.annotation()
    if ann["retries"] or ann["events"]:
        logging.getLogger(__name__).info(
            "supervisor: %s", json.dumps(ann, sort_keys=True))
    return result


def run_preflight_lint(report, strict: bool = False):
    """Print one lint report; returns ``(exit_code, annotation)`` —
    exit_code 0 means proceed (the annotation is stamped into result
    JSON), nonzero means the caller should abort the launch (strict
    mode with error-severity findings)."""
    print(report.render(), flush=True)
    rc = report.exit_code(strict=strict)
    if rc:
        print(f"lint: {report.errors} error-severity finding(s) — "
              "refusing to launch (--lint=strict)", flush=True)
        return rc, None
    return 0, report.annotation()


def apply_fused_bn(model, mode: Optional[str]):
    """Install the --fusedBN choice on a built model (no-op for
    None/'off'). Returns the model."""
    if mode and mode != "off":
        from bigdl_tpu.nn import set_bn_fused
        set_bn_fused(model, mode)
    return model


# the in-checkout compile cache: a fixed path (the directory is part of
# the cache key, so one that moved with ~, /tmp, a pid or a clock would
# never hit), git-ignored, inside the tree the chip tool copies
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> Optional[str]:
    """The persistent compile-cache dir this program sets in code: None
    where ``JAX_COMPILATION_CACHE_DIR`` is set (jax reads the variable
    itself, so the cache can be placed from outside and nothing here
    overrides it), else ``<checkout>/.jax_cache``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _REPO_CACHE


def enable_compile_cache() -> None:
    """Point jax at the persistent compile cache (every entry point —
    the CLIs via apply_platform, perf.run, bench children, fleet workers,
    the chip smoke — goes through here): a second process compiling the
    same program reads it back instead of recompiling."""
    cache = compile_cache_dir()
    if cache is None:
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)


def apply_platform(args) -> None:
    """Honor --platform BEFORE any jax backend init (the config API
    wins over a JAX_PLATFORMS variable in the environment). Also enables
    the persistent compile cache for every CLI."""
    platform = getattr(args, "platform", None)
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    enable_compile_cache()
    install_fault_plan(args)  # --faultPlan (no-op when unset)
    install_observability(args)  # --obs family (no-op when unset)
    mode = getattr(args, "autotune", None)
    if mode:
        from bigdl_tpu import tuning
        try:
            tuning.set_mode(mode)
        except ValueError as e:
            raise SystemExit(str(e))
    geom = getattr(args, "convGeom", None)
    if geom:
        # per-geometry decision file (apply_conv_probe.py --geom) — the
        # stem's wgrad can run NCHW while the 3x3 stages stay NHWC and
        # 1x1/s1 convs may run as GEMM; an explicit --convLayout below
        # still wins at lookup time
        from bigdl_tpu.ops.conv2d import install_geom_file
        try:
            n = install_geom_file(geom)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            raise SystemExit(f"--convGeom {geom}: {e}")
        logging.getLogger(__name__).info(
            "installed %d per-geometry conv layout decisions from %s",
            n, geom)
    spec = getattr(args, "convLayout", None)
    if spec:
        # explicit per-pass conv layouts (or 'auto'/'default') — wins
        # over the measured-decision auto-install the Optimizer does,
        # over --convGeom decisions and over the autotuner
        from bigdl_tpu.ops.conv2d import install_layout_spec
        try:
            install_layout_spec(spec)
        except ValueError as e:
            raise SystemExit(str(e))


def add_train_args(p: argparse.ArgumentParser) -> None:
    """The reference's common knobs (-f, -b, --learningRate, --maxEpoch,
    --checkpoint, --model/--state resume; models/lenet/Utils.scala flags)."""
    _add_platform_arg(p)
    p.add_argument("-f", "--folder", default="./", help="data folder")
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("--learningRate", type=float, default=0.05)
    p.add_argument("--learningRateDecay", type=float, default=0.0)
    p.add_argument("--weightDecay", type=float, default=0.0)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--maxEpoch", type=int, default=5)
    p.add_argument("--checkpoint", default=None,
                   help="dir for model.<n>/state.<n> snapshots")
    p.add_argument("--stepsPerDispatch", type=int, default=1,
                   help="scan K optimizer steps over K prefetched batches "
                        "inside one jitted program — amortizes the "
                        "~2.5-3.5 ms per-dispatch overhead of the "
                        "runtime (+1.6%% ResNet-50 throughput at K=10 on "
                        "the pre-PR-1 chip set-up, PERF.md). Update math and RNG "
                        "sequence identical to K=1; iteration-counted "
                        "triggers fire at the next dispatch boundary. "
                        "Single-device only")
    p.add_argument("--convLayout", default=None,
                   metavar="FWD,DGRAD,WGRAD",
                   help="per-pass conv activation layouts (NHWC|NCHW|"
                        "GEMM each, or 'auto'/'default'; GEMM = "
                        "dot_general for eligible 1x1/stride-1 convs, "
                        "exact-parity fallback elsewhere). Unset = "
                        "'auto': the measured probe decision shipped for "
                        "this device kind (ops/conv2d.MEASURED_DECISIONS, "
                        "+1.1%% ResNet-50 train throughput on TPU v5 "
                        "lite), no-op on unmeasured devices; 'default' "
                        "forces all-NHWC. Wins over --convGeom and the "
                        "autotuner")
    p.add_argument("--convGeom", default=None, metavar="FILE",
                   help="per-conv-geometry layout decision JSON "
                        "(scripts/apply_conv_probe.py --geom): decisions "
                        "keyed by (kh, kw, stride, cin, cout, groups, "
                        "dilation, dtype), each pass independently "
                        "NHWC/NCHW/GEMM")
    p.add_argument("--model", default=None,
                   help="checkpoint dir to resume model from")
    p.add_argument("--overWriteCheckpoint", action="store_true")
    p.add_argument("--keepCheckpoints", type=int, default=None,
                   metavar="K",
                   help="keep only the newest K checkpoint snapshots "
                        "(GC after each write; the newest checksum-"
                        "VALID pair is never deleted)")
    add_resilience_args(p)
    add_obs_args(p)
    add_pipeline_args(p)
    p.add_argument("--dataParallel", action="store_true",
                   help="shard the batch over all visible devices")
    add_strategy_arg(p)
    add_grad_comm_args(p)
    add_autotune_arg(p)
    add_fused_bn_arg(p)
    add_lint_arg(p)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--logEvery", type=int, default=10)
    p.add_argument("--summary", default=None, metavar="DIR",
                   help="append train/val JSONL curves to DIR")
    p.add_argument("--optimMethod", default="sgd",
                   choices=["sgd", "adam", "adamw", "adagrad", "rmsprop",
                            "lars", "lamb"],
                   help="optimizer (sgd keeps the reference defaults; "
                        "weightDecay/momentum apply where meaningful)")


def add_test_args(p: argparse.ArgumentParser) -> None:
    _add_platform_arg(p)
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("--model", required=True, help="checkpoint dir or file")


def setup_logging() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s - %(message)s")


# the --strategy surface (ISSUE 8): the five parallelism families the
# __graft_entry__.py dryruns validate, reachable from perf and the
# training CLIs instead of living only there
STRATEGY_CHOICES = ("dp", "tp", "sp", "pp", "ep")


def add_strategy_arg(p: argparse.ArgumentParser) -> None:
    p.add_argument("--strategy", default=None, metavar="NAME[:K]",
                   help="multi-device training strategy over every "
                        "visible device (bigdl_tpu.parallel): dp = data "
                        "parallel (ZeRO-1 sharded optimizer state), tp = "
                        "dp x Megatron tensor parallel, sp = dp x ring-"
                        "attention sequence parallel (transformer_lm* "
                        "models), pp = GPipe pipeline x dp "
                        "(transformer_lm* models), ep = expert-parallel "
                        "MoE. Optional :K sizes the non-data axis (e.g. "
                        "tp:4 = 4-way model parallel, pp:2 = 2 stages); "
                        "defaults mirror __graft_entry__.py's dryrun "
                        "shapes. CPU-testable end to end with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=8. "
                        "Replaces the deprecated --dataParallel "
                        "(still accepted as an alias for 'dp'). Mesh "
                        "topology and device count are stamped into "
                        "every result JSON line")


def parse_strategy_spec(spec: Optional[str]):
    """``"name[:K]"`` -> ``(name, k|None)``; SystemExit on junk (the
    clean-CLI-validation contract, ADVICE r5 #5)."""
    if not spec:
        return None, None
    name, _, k = str(spec).partition(":")
    if name not in STRATEGY_CHOICES:
        raise SystemExit(f"--strategy {spec!r}: unknown strategy "
                         f"{name!r}; choose from {list(STRATEGY_CHOICES)}"
                         " (optionally NAME:K to size the non-data axis)")
    if not k:
        return name, None
    try:
        kk = int(k)
    except ValueError:
        raise SystemExit(f"--strategy {spec!r}: K must be an integer")
    if kk < 1:
        raise SystemExit(f"--strategy {spec!r}: K must be >= 1")
    return name, kk


def resolve_strategy(args):
    """The run's effective ``(strategy_name, k|None)`` — ``--strategy``
    wins; the historical ``--dataParallel`` flag is kept as a deprecated
    alias for ``dp``."""
    name, k = parse_strategy_spec(getattr(args, "strategy", None))
    if name is not None:
        return name, k
    if getattr(args, "dataParallel", False):
        logging.getLogger(__name__).warning(
            "--dataParallel is deprecated; use --strategy dp")
        return "dp", None
    return None, None


def check_strategy_dispatch(steps: int, flag: str = "--stepsPerDispatch"):
    """The PR 1 validation contract: multi-step dispatch amortization is
    single-device by construction and refuses (clean SystemExit) to
    combine with a multi-device strategy — perf's old hidden
    data_parallel branch silently ignored this."""
    if steps and int(steps) > 1:
        raise SystemExit(
            f"{flag} > 1 is a single-device dispatch amortization (the "
            "stepsPerDispatch contract); it cannot be combined with a "
            "multi-device --strategy/--dataParallel (whose runtime "
            "pipelines dispatch already)")


def strategy_mesh_axes(name: str, n_devices: int, k: Optional[int] = None
                       ) -> dict:
    """Axis layout of one strategy over ``n_devices`` (the shapes of
    ``__graft_entry__.py``'s dryrun). ``k`` sizes the non-data axis;
    defaults: tp/sp split
    devices 2-way on data (n>=4), pp uses 4 stages (n%4==0) else 2, ep
    puts every device on the expert axis."""
    n = int(n_devices)
    if name == "dp":
        return {"data": n}
    if name in ("tp", "sp"):
        axis = "model" if name == "tp" else "seq"
        kk = k or (n // 2 if n >= 4 else n)
        if n % kk:
            raise SystemExit(f"--strategy {name}:{kk} needs the {axis} "
                             f"axis to divide {n} devices")
        return {"data": n // kk, axis: kk}
    if name == "pp":
        kk = k or (4 if n % 4 == 0 and n >= 4 else 2)
        if n % kk:
            raise SystemExit(f"--strategy pp:{kk} needs the stage count "
                             f"to divide {n} devices")
        return {"pipe": kk, "data": n // kk}
    if name == "ep":
        return {"expert": k or n}
    raise SystemExit(f"unknown strategy {name!r}")


# the serving --strategy surface (ISSUE 16): two orthogonal axes behind
# one front door — ``tp[:K]`` shards the model over K chips, ``dp[:N]``
# runs N independent engine replicas, ``dp:N+tp:K`` composes them (N
# replicas, each tensor-parallel over K chips). Unlike the training
# grammar above there is no implicit data axis: serving devices are
# partitioned, not meshed globally.
SERVING_STRATEGY_CHOICES = ("dp", "tp")


def parse_serving_strategy(spec: Optional[str], n_devices: int):
    """``"tp[:K] | dp[:N] | dp:N+tp:K"`` -> ``(replicas, tp_k)``.

    Defaults when the axis size is omitted: ``tp`` -> all visible
    devices on the model axis, ``dp`` -> one single-device replica per
    visible device. Validates ``replicas * tp_k <= n_devices`` with the
    XLA_FLAGS recipe in the error (the clean-CLI-validation contract).
    ``None``/empty spec -> ``(1, 1)`` (the single-chip path)."""
    n = int(n_devices)
    if not spec:
        return 1, 1
    replicas: Optional[int] = None
    tp_k: Optional[int] = None
    seen_dp = seen_tp = False
    for part in str(spec).split("+"):
        name, _, k = part.strip().partition(":")
        if name not in SERVING_STRATEGY_CHOICES:
            raise SystemExit(
                f"serve --strategy {spec!r}: unknown axis {name!r}; the "
                f"serving grammar is tp[:K], dp[:N], or dp:N+tp:K")
        try:
            kk = int(k) if k else None
        except ValueError:
            raise SystemExit(
                f"serve --strategy {spec!r}: axis size in {part!r} must "
                "be an integer")
        if kk is not None and kk < 1:
            raise SystemExit(
                f"serve --strategy {spec!r}: axis size in {part!r} must "
                "be >= 1")
        if name == "dp":
            if seen_dp:
                raise SystemExit(
                    f"serve --strategy {spec!r}: dp given twice")
            seen_dp, replicas = True, kk
        else:
            if seen_tp:
                raise SystemExit(
                    f"serve --strategy {spec!r}: tp given twice")
            seen_tp, tp_k = True, kk
    # resolve omitted axis sizes: a lone axis claims every visible
    # device; in the composed form the omitted one takes what the
    # explicit one leaves over
    if seen_tp and tp_k is None:
        tp_k = max(n // (replicas or 1), 1) if seen_dp else max(n, 1)
    if seen_dp and replicas is None:
        replicas = max(n // (tp_k or 1), 1) if seen_tp else max(n, 1)
    replicas, tp_k = replicas or 1, tp_k or 1
    if replicas * tp_k > n:
        need = replicas * tp_k
        shape = (f"{replicas} replicas x {tp_k}-way tp"
                 if seen_dp and seen_tp else
                 f"{tp_k}-way tp" if seen_tp else
                 f"one device per replica x {replicas} replicas")
        raise SystemExit(
            f"serve --strategy {spec!r} needs {need} devices ({shape}) "
            f"but only {n} are visible; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} to fake "
            "them")
    return replicas, tp_k


# the --gradCompress surface (ISSUE 10): the wire dtypes of the
# compressed gradient all-reduce, optionally error-compensated (must
# mirror parallel/grad_comm.COMPRESS_MODES — asserted in tests, not
# imported here, so argparse setup never pulls the jax-importing
# parallel package)
GRAD_COMPRESS_CHOICES = ("off", "bf16", "fp16", "bf16+ec", "fp16+ec")


def add_grad_comm_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--gradCompress", default="off",
                   choices=list(GRAD_COMPRESS_CHOICES),
                   help="compress the gradient all-reduce "
                        "(bigdl_tpu.parallel.grad_comm, the reference's "
                        "FP16CompressedTensor codec): gradients flatten "
                        "into size-bounded dense buckets, cross the wire "
                        "as bf16/fp16 (half the bytes), decompress to "
                        "f32 after; '+ec' adds the local rounding "
                        "residual back so optimizer math sees the exact "
                        "f32 gradient. Active under a multi-device "
                        "--strategy (dp/tp); 'off' is bit-identical to "
                        "the uncompressed step. Stamped into result "
                        "JSON as grad_compress/grad_buckets")
    p.add_argument("--gradBuckets", default="auto", metavar="auto|N",
                   help="dense-bucket bound for --gradCompress: 'auto' = "
                        "the tuned grad_comm decision when --autotune is "
                        "on, else the shipped 4 MiB default; an integer "
                        "N pins the bound to N MiB")


def make_grad_comm(args):
    """``(--gradCompress, --gradBuckets)`` -> GradCommConfig (None when
    the surface is untouched); SystemExit on junk (the clean-CLI-
    validation contract)."""
    compress = getattr(args, "gradCompress", None)
    buckets = getattr(args, "gradBuckets", None)
    if (compress or "off") == "off" and (buckets in (None, "auto")):
        return None
    from bigdl_tpu.parallel.grad_comm import make_config
    try:
        return make_config(compress, buckets)
    except ValueError as e:
        raise SystemExit(str(e))


def build_strategy(args, model=None):
    """Resolve ``--strategy``/``--dataParallel`` into a strategy object
    consumed by the Optimizer (the reference's Engine.init(node, cores)
    + DistriOptimizer path). Owns the validation the old perf branch
    skipped: the stepsPerDispatch/innerSteps x strategy SystemExit
    contract fires here, BEFORE any mesh is built. Returns None
    single-device (the deprecated alias degrades silently, an explicit
    --strategy exits with the XLA_FLAGS recipe). dp/tp build here;
    sp/pp/ep need harness-side model composition (ring attention /
    pipeline stack / MoE) and are wired in ``cli/perf.py``."""
    name, k = resolve_strategy(args)
    elastic = getattr(args, "elastic", None)
    if name is None:
        if elastic is not None:
            raise SystemExit("--elastic needs --strategy dp (elastic "
                             "reshape is a data-parallel contract)")
        return None
    import jax

    if elastic is not None and name != "dp":
        raise SystemExit(f"--elastic composes with --strategy dp only "
                         f"(got {name}); tp/sp/pp/ep meshes cannot "
                         "re-form at arbitrary surviving counts")
    # elastic runs build their mesh from the SURVIVING roster: after a
    # kill_device fault the retry's fresh strategy lands on fewer devices
    devices = None
    if elastic is not None:
        from bigdl_tpu.resilience.faults import healthy_devices
        devices = healthy_devices()
        n = len(devices)
    else:
        n = len(jax.devices())
    if n <= 1:
        if getattr(args, "strategy", None):
            raise SystemExit(
                f"--strategy {name} needs more than one device; off-chip "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "(the MULTICHIP dryrun recipe)")
        return None  # deprecated --dataParallel alias: historical no-op
    check_strategy_dispatch(getattr(args, "stepsPerDispatch", 1) or 1)
    check_strategy_dispatch(getattr(args, "innerSteps", 1) or 1,
                            "--innerSteps")
    from bigdl_tpu.parallel import DataParallel, TensorParallel, make_mesh

    grad_comm = make_grad_comm(args)
    axes = strategy_mesh_axes(name, n, k)
    if name == "dp":
        if elastic is not None:
            from bigdl_tpu.resilience.elastic import ElasticDataParallel
            return ElasticDataParallel(make_mesh(axes, devices),
                                       batch_policy=elastic,
                                       grad_comm=grad_comm)
        return DataParallel(make_mesh(axes), grad_comm=grad_comm)
    if name == "tp":
        if model is None:
            raise SystemExit("--strategy tp needs the model to derive "
                             "its Megatron sharding rules")
        t = TensorParallel(make_mesh(axes), model)
        # TensorParallel's ctor is (mesh, model); it inherits the
        # reduce_grads entry point, so the config rides the attribute
        t.grad_comm = grad_comm
        return t
    raise SystemExit(f"--strategy {name} composes with the model/step "
                     "structure and is wired through the perf harness "
                     "(bigdl-tpu perf --strategy {sp,pp,ep}); the "
                     "training CLIs support dp/tp")


def build_optimizer(model, dataset, criterion, args, schedule=None,
                    optim_method=None):
    from bigdl_tpu.optim import Optimizer, SGD, Trigger
    from bigdl_tpu.optim.schedules import Default

    # --fusedBN lever for every training CLI (the Optimizer auto-unfuses
    # with a warning under a multi-device strategy)
    apply_fused_bn(model, getattr(args, "fusedBN", None))

    if optim_method is None:
        sched = (schedule if schedule is not None
                 else Default(args.learningRateDecay))
        name = getattr(args, "optimMethod", "sgd")
        if name == "sgd":
            optim_method = SGD(
                learning_rate=args.learningRate,
                weight_decay=args.weightDecay,
                momentum=args.momentum, schedule=sched)
        else:
            from bigdl_tpu.optim import (Adagrad, Adam, AdamW, LAMB, LARS,
                                         RMSprop)
            lr = args.learningRate
            wd = args.weightDecay
            optim_method = {
                "adam": lambda: Adam(learning_rate=lr, schedule=sched),
                "adamw": lambda: AdamW(learning_rate=lr, weight_decay=wd,
                                       schedule=sched),
                # Adagrad/RMSprop carry their own decay knobs, no
                # schedule parameter (matching the reference's surface)
                "adagrad": lambda: Adagrad(
                    learning_rate=lr, weight_decay=wd,
                    lr_decay=args.learningRateDecay),
                "rmsprop": lambda: RMSprop(learning_rate=lr),
                "lars": lambda: LARS(learning_rate=lr, weight_decay=wd,
                                     momentum=args.momentum,
                                     schedule=sched),
                "lamb": lambda: LAMB(learning_rate=lr, weight_decay=wd,
                                     schedule=sched),
            }[name]()
    # build_strategy owns the stepsPerDispatch x strategy SystemExit
    # contract (ADVICE r5 #5) — one validator shared with perf (ISSUE 8)
    strategy = build_strategy(args, model=model)
    k = int(getattr(args, "stepsPerDispatch", 1) or 1)
    # --dataWorkers/--prefetchDepth/--stage: the async pipeline stack
    # wraps the dataset BEFORE the Optimizer sees it; built fresh per
    # supervised/elastic retry (run_optimize re-invokes make_optimizer),
    # so device staging always commits to the current attempt's mesh
    dataset, _ = build_feed(dataset, args, strategy=strategy)
    opt = Optimizer(model, dataset, criterion,
                    optim_method=optim_method,
                    end_when=Trigger.max_epoch(args.maxEpoch),
                    strategy=strategy, seed=args.seed,
                    log_every=args.logEvery,
                    steps_per_dispatch=k)
    if args.checkpoint:
        os.makedirs(args.checkpoint, exist_ok=True)
        opt.set_checkpoint(Trigger.every_epoch(), args.checkpoint,
                           overwrite=getattr(args, "overWriteCheckpoint",
                                             False),
                           keep_last=getattr(args, "keepCheckpoints",
                                             None))
    if args.model:
        opt.resume(args.model)
    if getattr(args, "summary", None):
        opt.set_summary(args.summary)
    lint_mode = getattr(args, "lint", None)
    if lint_mode:
        # pre-flight static analysis of the REAL step this Optimizer
        # will compile (bigdl_tpu.analysis.preflight_optimizer) —
        # module rules always, the jaxpr pass when the dataset exposes
        # its batch geometry; strict aborts before any compile
        from bigdl_tpu.analysis import preflight_optimizer
        rc, _ = run_preflight_lint(preflight_optimizer(opt),
                                   strict=(lint_mode == "strict"))
        if rc:
            raise SystemExit(rc)
    return opt


# ---------------------------------------------------------------------
# the ResolvedConfig spine (ISSUE 19 satellite, ROADMAP item 5): the
# mirrored flag families every CLI re-parses (--strategy/--gradCompress/
# --gradBuckets/--quantize/--speculate/--fusedBN/--convLayout/--convGeom/
# --autotune) resolved ONCE into a typed object that cli/lint.py and
# every --lint preflight hand to the analyzer — no per-CLI re-wiring.
import dataclasses


@dataclasses.dataclass(frozen=True)
class ResolvedConfig:
    """One run configuration, resolved from the shared flag surface.

    ``mesh_axes`` is the declared mesh (axis -> size) the strategy
    implies over ``n_devices`` — for the lint CLI with no real devices
    the virtual defaults below size it, so every multichip surface
    lints on a 1-CPU box."""

    model: str
    batch: int = 32
    seq: Optional[int] = None
    classes: int = 1000
    dtype: str = "bfloat16"
    fused_bn: Optional[str] = None
    conv_layout: Optional[str] = None
    conv_geom: Optional[str] = None
    autotune: str = "off"
    strategy: Optional[str] = None
    strategy_k: Optional[int] = None
    n_devices: int = 1
    mesh_axes: tuple = ()            # ((axis, size), ...) — hashable
    grad_compress: str = "off"
    grad_buckets: str = "auto"
    quantize: Optional[str] = None
    speculate: int = 0
    kv_page_tokens: Optional[int] = None
    slots: int = 4
    lint_mode: Optional[str] = None
    trace: bool = True
    # serve/fleet topology (ISSUE 20 satellite): the serving grammar's
    # resolution (dp replicas x tp shards) and the fleet width, owned
    # here so serve, fleet, and worker never re-mirror the parse
    serving_replicas: int = 1
    serving_tp: int = 1
    fleet_workers: int = 0

    @property
    def mesh(self) -> dict:
        return dict(self.mesh_axes)

    def make_grad_comm(self):
        """The GradCommConfig this run would build (None when the
        --gradCompress surface is untouched)."""
        if (self.grad_compress or "off") == "off" \
                and self.grad_buckets in (None, "auto"):
            return None
        from bigdl_tpu.parallel.grad_comm import make_config
        try:
            return make_config(self.grad_compress, self.grad_buckets)
        except ValueError as e:
            raise SystemExit(str(e))

    def describe(self) -> dict:
        """Provenance dict (result-JSON / lint-report annotation)."""
        out = {"model": self.model, "batch": self.batch}
        if self.strategy:
            out["strategy"] = (f"{self.strategy}:{self.strategy_k}"
                               if self.strategy_k else self.strategy)
            out["mesh"] = ",".join(f"{a}:{s}" for a, s in self.mesh_axes)
        if (self.grad_compress or "off") != "off":
            out["grad_compress"] = self.grad_compress
        if self.quantize:
            out["quantize"] = self.quantize
        if self.speculate:
            out["speculate"] = self.speculate
        if self.kv_page_tokens:
            out["kv_page_tokens"] = self.kv_page_tokens
        if self.serving_replicas > 1 or self.serving_tp > 1:
            out["serving_replicas"] = self.serving_replicas
            out["serving_tp"] = self.serving_tp
        if self.fleet_workers:
            out["fleet_workers"] = self.fleet_workers
        return out


def _virtual_mesh_devices(name: str, k: Optional[int]) -> tuple:
    """(n_devices, k) sized for an abstract lint with no real devices:
    enough virtual chips that the strategy's default shape exists."""
    if name == "dp":
        return 8, None
    if name in ("tp", "sp"):
        kk = k or 4
        return 2 * kk, kk
    if name == "pp":
        kk = k or 2
        return 2 * kk, kk
    if name == "ep":
        return (k or 8), (k or 8)
    raise SystemExit(f"unknown strategy {name!r}")


def resolve_lint_config(args, *, n_devices: Optional[int] = None
                        ) -> ResolvedConfig:
    """Resolve the shared flag families on ``args`` into one
    :class:`ResolvedConfig`. ``n_devices=None`` (the lint CLI: no real
    mesh) sizes the strategy over virtual devices —
    ``AbstractMesh``-traced, so nothing is allocated; a preflight on a
    real run passes its actual device count."""
    name, k = parse_strategy_spec(getattr(args, "strategy", None))
    mesh_axes: tuple = ()
    n = int(n_devices or 1)
    if name is not None:
        if n_devices is None:
            n, k = _virtual_mesh_devices(name, k)
        axes = strategy_mesh_axes(name, n, k)
        mesh_axes = tuple((str(a), int(s)) for a, s in axes.items())
    quantize = getattr(args, "quantize", None)
    if quantize:
        from bigdl_tpu.serving.quant import parse_quantize
        try:
            parse_quantize(quantize)  # validate the spelling up front
        except ValueError as e:
            raise SystemExit(f"--quantize {quantize!r}: {e}")
    return ResolvedConfig(
        model=getattr(args, "model", None) or "",
        batch=int(getattr(args, "batchSize", 32) or 32),
        seq=getattr(args, "seq", None),
        classes=int(getattr(args, "classes", 1000) or 1000),
        dtype=("float32" if getattr(args, "f32", False) else "bfloat16"),
        fused_bn=getattr(args, "fusedBN", None),
        conv_layout=getattr(args, "convLayout", None),
        conv_geom=getattr(args, "convGeom", None),
        autotune=getattr(args, "autotune", "off") or "off",
        strategy=name, strategy_k=k, n_devices=n, mesh_axes=mesh_axes,
        grad_compress=getattr(args, "gradCompress", "off") or "off",
        grad_buckets=getattr(args, "gradBuckets", "auto") or "auto",
        quantize=quantize,
        speculate=int(getattr(args, "speculate", 0) or 0),
        # serve spells --kvPageTokens 'auto' too; lint needs a number
        kv_page_tokens=(int(kvp) if (kvp := getattr(
            args, "kvPageTokens", None)) and str(kvp).lstrip("-").isdigit()
            else None),
        slots=int(getattr(args, "slots", 4) or 4),
        lint_mode=getattr(args, "lint", None),
        trace=not getattr(args, "no_trace", False))


def _virtual_serving_devices(spec: Optional[str]) -> int:
    """Device count for resolving a serving strategy ABSTRACTLY — in a
    process with no accelerator client (the fleet router) or no devices
    at all (lint). Big enough that any explicit ``dp:N+tp:K`` shape
    exists; omitted axis sizes then default over the same count a CPU
    smoke run would fake with XLA_FLAGS."""
    need = 1
    for part in str(spec or "").split("+"):
        _, _, k = part.strip().partition(":")
        if k and str(k).lstrip("-").isdigit():
            need *= max(int(k), 1)
    return max(8, need)


def resolve_serve_config(args, *, n_devices: Optional[int] = None
                         ) -> ResolvedConfig:
    """The serve/fleet half of the ResolvedConfig spine (ISSUE 20
    satellite): resolve the serving flag surface — topology via the
    SERVING grammar (``tp[:K] | dp[:N] | dp:N+tp:K``, not the training
    grammar), quantize/speculate modes, fleet width — ONCE, so the
    serve CLI, the fleet router, and every worker agree on one parse.

    ``n_devices=None`` resolves abstractly over virtual devices: the
    router process calls this before any worker boots (catching a bad
    --strategy/--quantize/--speculate without paying K engine compiles)
    and must never initialize jax itself."""
    spec = getattr(args, "strategy", None)
    n = int(n_devices) if n_devices is not None \
        else _virtual_serving_devices(spec)
    replicas, tp_k = parse_serving_strategy(spec, n)
    quantize = getattr(args, "quantize", None)
    if quantize == "off":  # serve spells the default as the string off
        quantize = None
    if quantize:
        from bigdl_tpu.serving.quant import parse_quantize
        try:
            parse_quantize(quantize)
        except ValueError as e:
            raise SystemExit(f"--quantize {quantize!r}: {e}")
    speculate = int(getattr(args, "speculate", 0) or 0)
    if speculate < 0:
        raise SystemExit(f"--speculate {speculate}: draft length must "
                         "be >= 0")
    fleet = int(getattr(args, "fleet", 0) or 0)
    if fleet < 0:
        raise SystemExit(f"--fleet {fleet}: worker count must be >= 0")
    mesh_axes: tuple = ()
    if tp_k > 1:
        mesh_axes = (("model", int(tp_k)),)
    return ResolvedConfig(
        model=getattr(args, "model", None) or "",
        batch=int(getattr(args, "batchSize", 32) or 32),
        seq=getattr(args, "seq", None),
        dtype=("float32" if getattr(args, "f32", False) else "bfloat16"),
        strategy=spec or None,
        n_devices=n, mesh_axes=mesh_axes,
        quantize=quantize, speculate=speculate,
        kv_page_tokens=(int(kvp) if (kvp := getattr(
            args, "kvPageTokens", None)) and str(kvp).lstrip("-").isdigit()
            else None),
        slots=int(getattr(args, "slots", 4) or 4),
        lint_mode=getattr(args, "lint", None),
        serving_replicas=int(replicas), serving_tp=int(tp_k),
        fleet_workers=fleet)


def load_trained(model, path: str):
    """Load params/mod_state from a checkpoint dir (newest model.<n>) or a
    single saved file (reference Module.load, nn/Module.scala:28)."""
    from bigdl_tpu.utils.file import load_pytree, latest_checkpoint

    if os.path.isdir(path):
        p = latest_checkpoint(path, "model.")
        if p is None:
            raise FileNotFoundError(f"no model.<n> checkpoint in {path}")
    else:
        p = path
    blob = load_pytree(p)
    return blob["params"], blob["mod_state"]


def evaluate(model, params, mod_state, dataset,
             methods: Optional[Sequence] = None):
    """Standalone evaluation (reference optim/Validator.scala +
    models/*/Test.scala)."""
    from bigdl_tpu.optim import Top1Accuracy
    from bigdl_tpu.optim.validator import build_eval_fn, run_evaluation

    methods = list(methods) if methods else [Top1Accuracy()]
    eval_fn = build_eval_fn(model, methods, None)
    results = run_evaluation(eval_fn, dataset, methods, params, mod_state,
                             None)
    for m, r in zip(methods, results):
        print(f"{m.name} is {r!r}")
    return results
