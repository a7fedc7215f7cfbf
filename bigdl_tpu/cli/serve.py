"""`bigdl-tpu serve` — the online inference endpoint (ISSUE 5).

Serves a perf-zoo model (or a custom-dims transformer_lm) from a
training checkpoint over HTTP with dynamic micro-batching, bucketed
compiles, and (for LMs) continuous-batching KV-cache decode:

    bigdl-tpu serve lenet5 --model ckpt_dir --port 8000
    bigdl-tpu serve resnet50 --model ckpt_dir --fusedBN apply \
        --autotune cached --buckets 1,2,4,8,16,32
    bigdl-tpu serve transformer_lm --model ckpt_dir --slots 8 --bf16
    bigdl-tpu serve phi4_mini_flash --randomInit --bf16 --slots 64 \
        --buckets 1 --seq 256
    bigdl-tpu serve smallthinker --randomInit --bf16 --slots 32 \
        --buckets 1 --seq 256
    bigdl-tpu serve solar_open2 --randomInit --bf16 --slots 64 \
        --buckets 1 --seq 256
    curl -d '{"tokens": [3, 1, 4], "max_new_tokens": 8}' \
        localhost:8000/generate

The config flags mirror the perf harness (`--fusedBN`, `--convLayout`,
`--convGeom`, `--autotune`, `--lint`) so the served program is the SAME
tuned program the benchmarks measured, and the resolved configuration is
stamped into every `/metrics` scrape (the perf-JSON provenance contract,
extended to serving).
"""

from __future__ import annotations

import argparse

from bigdl_tpu.cli import common


def _parse_buckets(spec: str):
    try:
        out = tuple(sorted({int(t) for t in spec.split(",") if t.strip()}))
        if not out or out[0] < 1:
            raise ValueError
        return out
    except ValueError:
        raise SystemExit(f"--buckets {spec!r}: expected a comma-separated "
                         f"list of positive ints, e.g. 1,2,4,8,16,32")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "bigdl-tpu serve",
        description="online inference over HTTP (bigdl_tpu.serving): "
                    "bucketed compiles, dynamic micro-batching, KV-cache "
                    "decode for LMs, /metrics with config provenance")
    p.add_argument("model",
                   help="perf model-zoo name (see `bigdl-tpu perf`), e.g. "
                        "lenet5, resnet50, transformer_lm")
    p.add_argument("--model", dest="checkpoint", default=None,
                   metavar="CKPT",
                   help="training checkpoint to serve: dir with model.<n> "
                        "(single-blob or sharded orbax) or a single file; "
                        "optimizer state is never loaded")
    p.add_argument("--randomInit", action="store_true",
                   help="serve freshly initialized weights (benchmarks / "
                        "smoke tests; refuses to default silently)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("-p", "--port", type=int, default=8000,
                   help="0 = ephemeral (the chosen port is printed)")
    p.add_argument("--fleet", type=int, default=0, metavar="K",
                   help="serving fleet (ISSUE 20): run K engine WORKER "
                        "PROCESSES behind a router on this port — SLO-"
                        "burn-weighted least-loaded routing, supervised "
                        "restart of dead workers, rolling zero-downtime "
                        "weight swap via POST /admin/reload. 0 (default) "
                        "= today's single process")
    p.add_argument("--modelVersion", default=None, metavar="TAG",
                   help="version tag for the served weights — stamped "
                        "into provenance and echoed as x-model-version "
                        "on every response; bumped by /admin/reload "
                        "(default v0)")
    p.add_argument("--fleetHeartbeatS", type=float, default=0.5,
                   help="router -> worker heartbeat poll interval")
    p.add_argument("--fleetRestartBudget", type=int, default=8,
                   help="supervised restarts per worker before the "
                        "router gives up on that slot (exponential "
                        "backoff between attempts)")
    p.add_argument("--strategy", default=None, metavar="SPEC",
                   help="multi-chip serving (ISSUE 16): 'tp[:K]' shards "
                        "the model over K chips (Megatron layout, "
                        "bit-identical greedy output), 'dp[:N]' runs N "
                        "independent engine replicas behind one front "
                        "door (least-loaded routing, per-replica "
                        "/metrics labels), 'dp:N+tp:K' composes them. "
                        "Omitted sizes take all visible devices. "
                        "Default: single-device, exactly as before")
    p.add_argument("--buckets", default="1,2,4,8,16,32",
                   help="batch-size buckets the engine pre-compiles; "
                        "requests pad up to the nearest (bounded compile "
                        "cache, metered padding waste)")
    p.add_argument("--maxBatch", type=int, default=32,
                   help="micro-batcher flush size (throughput trigger)")
    p.add_argument("--maxWaitMs", type=float, default=5.0,
                   help="oldest-row age that forces a flush (latency "
                        "trigger)")
    p.add_argument("--maxQueue", type=int, default=256,
                   help="admission control: queued rows beyond this are "
                        "fast-rejected with HTTP 429")
    p.add_argument("--slots", type=int, default=4,
                   help="continuous-batching decode slots (LM models): "
                        "concurrent generations sharing one decode batch")
    p.add_argument("--maxWaiting", type=int, default=64,
                   help="generate requests allowed to wait for a slot "
                        "before 429")
    p.add_argument("--seq", type=int, default=None,
                   help="override the LM sequence length / max context")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="speculative decoding: a draft LM proposes K "
                        "tokens per round, the target verifies them in "
                        "one chunked dispatch (exact acceptance — greedy "
                        "output is bit-identical to --speculate 0). "
                        "Default draft is the target itself (self-draft); "
                        "pass --draftDims for a smaller proposer")
    p.add_argument("--draftDims", default=None,
                   metavar="DMODEL,LAYERS,HEADS",
                   help="draft-model dims for --speculate (randomly "
                        "initialized; acceptance stays exact, only the "
                        "accept RATE depends on draft quality)")
    p.add_argument("--kvPageTokens", default=None, metavar="N|auto",
                   help="paged KV cache: fixed pages of N tokens with "
                        "per-slot page tables — kv_cache_bytes then "
                        "tracks ALLOCATED pages, not slots x max_len. "
                        "'auto' consults the kv_pages autotune namespace "
                        "(falls back to 128 where it divides max_len)")
    p.add_argument("--prefixCache", action="store_true",
                   help="share page-aligned prompt-prefix K/V across "
                        "requests (needs --kvPageTokens): hits copy "
                        "resident pages and prefill only the suffix")
    p.add_argument("--quantize", default="off",
                   choices=("off", "int8", "fp8", "kv8", "int8+kv8",
                            "fp8+kv8"),
                   help="quantized serving (ISSUE 17): int8/fp8 weights "
                        "(per-channel symmetric, dequant fused into the "
                        "matmul epilogue), kv8 stores the paged KV pools "
                        "8-bit with per-row scales (~2x the slots at "
                        "equal HBM; implies --kvPageTokens, auto-picked "
                        "if unset). Greedy-agreement + logit-error vs "
                        "f32 are measured at startup and stamped into "
                        "provenance. 'off' is byte-identical to today")
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--bf16", action="store_true",
                   help="bf16 activations (vision: input cast; LM: "
                        "post-embedding cast + bf16 KV cache)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-compiling every bucket at startup "
                        "(first requests then pay the compiles)")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="per-request wall timeout (503 past it)")
    p.add_argument("--deadlineMs", type=float, default=None,
                   help="default per-request deadline: rows/requests "
                        "still queued past it are dropped BEFORE "
                        "compute and answered 504 (a request-body "
                        "'deadline_ms' overrides per request)")
    p.add_argument("--shedAt", type=float, default=0.75,
                   help="tiered overload degradation: past this "
                        "fraction of queue capacity /generate sheds "
                        "with 429 while /predict keeps admitting")
    p.add_argument("--watchdogStallS", type=float, default=30.0,
                   help="watchdog verdict threshold: a worker busy with "
                        "no heartbeat this long is declared wedged — "
                        "pending requests fail fast (503) and /readyz "
                        "goes 503 while /healthz stays 200")
    p.add_argument("--faultPlan", default=None, metavar="SPEC|FILE",
                   help="deterministic fault injection on the serving "
                        "path (bigdl_tpu.resilience.faults), e.g. "
                        "'worker_kill@infer:3' kills the batcher worker "
                        "on its 3rd flush — the watchdog/fast-fail "
                        "drill. No-op unless set")
    p.add_argument("--reqTrace", choices=("on", "off"), default="off",
                   help="per-request lifecycle tracing (ISSUE 15): "
                        "request IDs minted at admission and threaded "
                        "through batcher/engine/decoder, server-side "
                        "TTFT/TPOT/ITL + queue/prefill/decode "
                        "histograms, a bounded flight recorder behind "
                        "/debug/requests + /debug/slots, and request "
                        "spans joined onto the --obs Chrome trace. "
                        "Off: the hot loop is byte-identical (same "
                        "None-check contract as --obs)")
    p.add_argument("--reqTraceCapacity", type=int, default=1024,
                   metavar="N",
                   help="completed-request records the flight recorder "
                        "retains (oldest dropped and counted past it)")
    p.add_argument("--slo", default=None, metavar="SPEC",
                   help="server-side latency SLOs, e.g. "
                        "'ttft=200,tpot=30' (ms; optional "
                        "burn=FRAC,window=N): per-dimension violation "
                        "counters, goodput, and tiered shed consults "
                        "the SLO burn rate. Implies --reqTrace on")
    p.add_argument("--accessLog", default=None, metavar="FILE",
                   help="append one JSONL access-log line per "
                        "completed request (rid, endpoint, state, "
                        "status, ttft/tpot/queue/prefill/decode ms, "
                        "tokens). Implies --reqTrace on")
    p.add_argument("--logSample", type=float, default=1.0, metavar="P",
                   help="access-log sampling probability in [0,1] — "
                        "deterministic per request id (hash-based), so "
                        "reruns sample the same rids")
    # custom-dims LM (matches cli/transformerlm.py checkpoints)
    p.add_argument("--vocabSize", type=int, default=None,
                   help="build a custom transformer_lm (with --dModel/"
                        "--numLayers/--numHeads/--seq) instead of the "
                        "32k-vocab perf-zoo config — the shape "
                        "`bigdl-tpu transformerlm train` checkpoints")
    p.add_argument("--dModel", type=int, default=128)
    p.add_argument("--numLayers", type=int, default=2)
    p.add_argument("--numHeads", type=int, default=4)
    common._add_platform_arg(p)
    common.add_autotune_arg(p)
    common.add_fused_bn_arg(p)
    common.add_lint_arg(p)
    p.add_argument("--convLayout", default=None, metavar="FWD,DGRAD,WGRAD",
                   help="per-pass conv activation layouts "
                        "(NHWC|NCHW|GEMM each, or 'auto'/'default') — "
                        "same semantics as the perf harness")
    p.add_argument("--convGeom", default=None, metavar="FILE",
                   help="per-conv-geometry layout decision JSON "
                        "(scripts/apply_conv_probe.py --geom)")
    return p


def _resolve_page_tokens(args, model, compute_dtype):
    """``--kvPageTokens``: explicit int, 'auto' (tuned via the kv_pages
    autotune namespace with a 128-where-it-divides fallback), or None
    (dense cache)."""
    spec = getattr(args, "kvPageTokens", None)
    if not spec:
        return None
    max_len = args.seq or model.max_len
    if str(spec).lower() == "auto":
        import jax.numpy as jnp

        from bigdl_tpu import tuning
        head_dim = getattr(model.encoder._modules[0].mha, "head_dim",
                           model.d_model // 4)
        kv_heads = getattr(model.encoder._modules[0].mha, "num_kv_heads",
                           args.numHeads)
        pt = tuning.kv_page_tokens(max_len, kv_heads, head_dim,
                                   compute_dtype or jnp.float32)
        if pt is None:  # autotune off: shipped ladder default
            for cand in (128, 64, 32, 256):
                if cand <= max_len and max_len % cand == 0:
                    return cand
            return None  # ragged max_len: stay dense
        return pt
    try:
        pt = int(spec)
    except ValueError:
        raise SystemExit(f"--kvPageTokens {spec!r}: expected an int or "
                         "'auto'")
    if pt < 1 or max_len % pt:
        raise SystemExit(f"--kvPageTokens {pt} must divide the context "
                         f"length {max_len}")
    return pt


def build_app(args):
    """Construct (app, engine, in_shape, in_dtype) from parsed args —
    separated from main() so tests and the load generator can run the
    server in-process on an ephemeral port."""
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.serving import (DecodeEngine, InferenceEngine,
                                   MetricsRegistry, MicroBatcher,
                                   ServingApp, Watchdog)

    name = args.model
    if args.vocabSize is not None and name.startswith("transformer_lm"):
        from bigdl_tpu import models
        seq = args.seq or 128
        model = models.transformer_lm(
            args.vocabSize, d_model=args.dModel,
            num_layers=args.numLayers, num_heads=args.numHeads,
            max_len=seq)
        in_shape = (seq,)
    else:
        from bigdl_tpu.cli.perf import build_model
        model, in_shape = build_model(name, class_num=args.classes,
                                      seq_len=args.seq)
    # an LM is what offers DecodeEngine its two programs, whatever its name
    is_lm = hasattr(model, "prefill_logits")
    common.apply_fused_bn(model, getattr(args, "fusedBN", None))
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    if is_lm and compute_dtype is not None:
        model.compute_dtype = compute_dtype  # post-embedding cast

    # --strategy (ISSUE 16): tp shards each engine over K chips, dp
    # runs N independent replicas on disjoint device groups; composed,
    # each replica is a K-chip tp engine. The parse itself lives on the
    # ResolvedConfig spine (ISSUE 20 satellite) so serve, fleet, and
    # lint resolve the serving flag surface identically.
    strategy = getattr(args, "strategy", None)
    n_replicas, tp_k, groups, mesh0 = 1, 1, None, None
    if strategy:
        import jax

        from bigdl_tpu.serving import replica_device_groups, serving_mesh
        cfg = common.resolve_serve_config(args,
                                          n_devices=len(jax.devices()))
        n_replicas, tp_k = cfg.serving_replicas, cfg.serving_tp
        groups = replica_device_groups(n_replicas, tp_k)
        mesh0 = serving_mesh(groups[0])

    state = getattr(model, "recurrent_state", False)
    ring = bool(getattr(model, "window", None))
    if (state or ring) and (
            args.kvPageTokens or args.prefixCache or args.speculate
            or (getattr(args, "quantize", None) or "off") != "off"
            or strategy):
        raise SystemExit(
            f"{name} keeps "
            + ("recurrent state" if state else "window rings")
            + " in its decode slots"
            + (" and routed expert stacks in its layers"
               if getattr(model, "routed_experts", False) else "")
            + " and serves on the dense path only: --kvPageTokens, "
            "--prefixCache, "
            "--speculate, --quantize and --strategy are not supported "
            "for it yet (serving/decode.py)")

    if args.checkpoint:
        if mesh0 is not None:
            # any training topology -> this serving topology (PR 10's
            # resharded restore; engines re-place per replica/mesh)
            from bigdl_tpu.serving import restore_for_serving
            params, mod_state = restore_for_serving(args.checkpoint,
                                                    mesh0)
        else:
            from bigdl_tpu.utils.orbax_ckpt import restore_for_inference
            params, mod_state = restore_for_inference(args.checkpoint)
    elif args.randomInit:
        import jax
        params, mod_state = model.init(jax.random.PRNGKey(0)), None
    else:
        raise SystemExit(
            "serve needs weights: pass --model CKPT (a training "
            "checkpoint dir or file) or --randomInit for smoke/bench "
            "runs")

    # --quantize (ISSUE 17): quantize the weight tree ONCE up front (the
    # engines re-apply idempotently, so dp replicas share the 8-bit
    # tree) and measure the quality guardrail against the full-precision
    # tree while it is still around. 'off' never touches params.
    quantize = getattr(args, "quantize", None) or "off"
    q_wfmt, q_kv8, quant_info = None, False, None
    if quantize != "off":
        from bigdl_tpu.serving.quant import (parse_quantize, quant_report,
                                             quantize_params)
        q_wfmt, q_kv8 = parse_quantize(quantize)
        if q_kv8 and not is_lm:
            raise SystemExit("--quantize kv8 quantizes the decode KV "
                             "cache — transformer_lm models only")
        qparams = quantize_params(params, q_wfmt)
        if is_lm:
            probe = list(range(1, min(9, model.vocab)))
            quant_info = quant_report(model, params, qparams,
                                      prompt=probe, max_new_tokens=8,
                                      kv8=q_kv8,
                                      cache_dtype=compute_dtype)
        params = qparams

    metrics = MetricsRegistry()
    # install as the process-global registry (ISSUE 7): resilience
    # fault/retry counters and any training-side phase publishes in this
    # process land on the SAME /metrics page the server exposes
    from bigdl_tpu.obs.metrics import set_registry
    set_registry(metrics)

    # --reqTrace (ISSUE 15): the per-request lifecycle tracer. --slo and
    # --accessLog imply it — asking for SLOs or an access log without
    # the recorder they read from would silently do nothing.
    reqtrace_on = (args.reqTrace == "on" or args.slo is not None
                   or args.accessLog is not None)
    reqtracer = None
    if reqtrace_on:
        from bigdl_tpu.serving import reqtrace as _reqtrace
        slo = None
        if args.slo is not None:
            try:
                slo = _reqtrace.SloPolicy.parse(args.slo)
            except ValueError as e:
                raise SystemExit(f"--slo {args.slo!r}: {e}")
        access_log = None
        if args.accessLog is not None:
            if not 0.0 <= args.logSample <= 1.0:
                raise SystemExit(f"--logSample {args.logSample} must be "
                                 "in [0, 1]")
            access_log = _reqtrace.AccessLog(args.accessLog,
                                             sample=args.logSample)
        reqtracer = _reqtrace.RequestTracer(
            capacity=args.reqTraceCapacity, metrics=metrics, slo=slo,
            access_log=access_log)
        _reqtrace.set_request_tracer(reqtracer)
    in_dtype = np.int32 if is_lm else np.float32
    lint_mode = getattr(args, "lint", None)
    page_tokens = None
    draft_model = draft_params = None
    if is_lm:
        page_tokens = _resolve_page_tokens(args, model, compute_dtype)
        if q_kv8 and page_tokens is None:
            # kv8 is a page-pool layout; pick a page size automatically
            # rather than bounce the operator to --kvPageTokens
            for cand in (128, 64, 32, 256):
                if model.max_len % cand == 0:
                    page_tokens = cand
                    break
            if page_tokens is None:
                raise SystemExit(
                    f"--quantize {quantize}: no page size in "
                    f"(128, 64, 32, 256) divides max_len "
                    f"{model.max_len}; pass --kvPageTokens explicitly")
        if args.prefixCache and page_tokens is None:
            raise SystemExit("--prefixCache needs --kvPageTokens (prefix "
                             "sharing is a page copy)")
        if args.speculate > 0 and args.draftDims:
            import jax

            from bigdl_tpu import models
            from bigdl_tpu.serving import parse_draft_dims
            dims = parse_draft_dims(args.draftDims)
            draft_model = models.transformer_lm(
                model.vocab, max_len=model.max_len,
                compute_dtype=compute_dtype, **dims)
            draft_params = draft_model.init(jax.random.PRNGKey(1))

    # what mesh the first-stack lint pass actually vetted (stamped into
    # provenance as lint_mesh — ISSUE 19 satellite)
    lint_prov: dict = {}

    def _build_stack(mesh, m, first):
        """One replica's full serving stack. ``m`` is its metrics view
        (labelled per replica under dp); pre-flight lints run for the
        FIRST stack only — replicas compile the identical graph."""
        engine = InferenceEngine(
            model, params, mod_state,
            buckets=_parse_buckets(args.buckets),
            compute_dtype=compute_dtype, lint=lint_mode,
            metrics=m, mesh=mesh, quantize=quantize)
        if first:
            # lint pre-flight over the exact serving graph BEFORE first
            # compile (strict refuses to serve, same contract as the
            # perf/training CLIs)
            rc = engine.preflight_lint(in_shape, in_dtype)
            if rc:
                raise SystemExit(rc)
            if lint_mode is not None and tp_k > 1:
                # tp placement rule (ISSUE 16): a big matmul weight the
                # Megatron pairing left replicated defeats the strategy
                from bigdl_tpu.analysis import run_serving_tp_rules
                report = run_serving_tp_rules(engine.params, tp_k)
                rc, _ = common.run_preflight_lint(
                    report, strict=(lint_mode == "strict"))
                if rc:
                    raise SystemExit(rc)
        batcher = MicroBatcher(engine.predict_scores,
                               max_batch=args.maxBatch,
                               max_wait_ms=args.maxWaitMs,
                               max_queue=args.maxQueue, metrics=m)
        decoder = None
        if is_lm:
            decoder = DecodeEngine(model, params, slots=args.slots,
                                   cache_dtype=compute_dtype,
                                   max_waiting=args.maxWaiting,
                                   metrics=m,
                                   kv_page_tokens=page_tokens,
                                   speculate=args.speculate,
                                   draft_model=draft_model,
                                   draft_params=draft_params,
                                   prefix_cache=args.prefixCache,
                                   mesh=mesh, quantize=quantize)
            # decode-path lint pre-flight (ISSUE 14): sampling-sort /
            # host-sync rules over the traced decode step + the
            # page-layout fit, same strict contract as the forward's.
            # Under dp:N+tp:K every replica compiles the IDENTICAL
            # graph on an isomorphic tp group, so linting the first
            # stack covers the fleet (ISSUE 19 bugfix) — the mesh the
            # pass actually checked is stamped into provenance as
            # lint_mesh so "which graph was vetted" is auditable
            if first and lint_mode is not None:
                from bigdl_tpu.analysis import (run_decode_rules,
                                                run_kv_sharding_rules,
                                                run_sharding_rules)
                # the page-layout fit is the one rule that reads it
                head_dim = page_tokens and getattr(
                    model.encoder._modules[0].mha, "head_dim",
                    model.d_model // 4)
                step_jaxpr = decoder.trace_step_jaxpr()
                report = run_decode_rules(
                    step_jaxpr, page_tokens=page_tokens,
                    max_len=decoder.max_len, head_dim=head_dim,
                    dtype=decoder.cache_dtype)
                if tp_k > 1:
                    # shardlint over the SHARDED decode step (ISSUE
                    # 19): annotation consistency on the tp group +
                    # the KV head-split fit of the page pools
                    run_sharding_rules(
                        step_jaxpr, mesh_axes={"model": tp_k},
                        strategy=None, context="serving",
                        report=report)
                    run_kv_sharding_rules(
                        decoder._kv.pools if decoder.paged
                        else decoder._cache,
                        tp_k, page_tokens=page_tokens, report=report)
                    lint_prov["lint_mesh"] = (
                        f"model:{tp_k} x {n_replicas} replica(s)"
                        if n_replicas > 1 else f"model:{tp_k}")
                else:
                    lint_prov["lint_mesh"] = (
                        f"replicated x {n_replicas} replica(s)"
                        if n_replicas > 1 else "single-device")
                rc, _ = common.run_preflight_lint(
                    report, strict=(lint_mode == "strict"))
                if rc:
                    raise SystemExit(rc)
            decoder.start()
        # watchdog over every worker thread: dead/wedged -> pending
        # futures fail fast, /readyz flips 503, /healthz stays (ISSUE 6)
        watchdog = Watchdog(stall_timeout_s=args.watchdogStallS,
                            metrics=m)
        watchdog.watch("batcher", batcher)
        if decoder is not None:
            watchdog.watch("decoder", decoder)
        watchdog.start()
        return engine, batcher, decoder, watchdog

    replica_set = None
    if n_replicas > 1:
        from bigdl_tpu.serving import Replica, ReplicaSet, serving_mesh
        reps = []
        for r in range(n_replicas):
            mesh_r = serving_mesh(groups[r])
            m = metrics.labelled(replica=str(r))
            eng_r, bat_r, dec_r, wd_r = _build_stack(mesh_r, m,
                                                     first=(r == 0))
            reps.append(Replica(r, devices=groups[r], mesh=mesh_r,
                                engine=eng_r, batcher=bat_r,
                                decoder=dec_r, watchdog=wd_r,
                                metrics=m))
        replica_set = ReplicaSet(reps, metrics=metrics)
        engine, batcher = reps[0].engine, None
        decoder, watchdog = reps[0].decoder, None
    else:
        engine, batcher, decoder, watchdog = _build_stack(
            mesh0, metrics, first=True)

    import jax
    prov = engine.provenance()
    if lint_prov:
        prov.update(lint_prov)

    def device_ids(params):
        """Ids of the devices a weight tree really lives on (read off
        the committed arrays, not the placement plan)."""
        return ",".join(str(i) for i in sorted(
            {d.id for leaf in jax.tree_util.tree_leaves(params)
             if isinstance(leaf, jax.Array) for d in leaf.devices()}))

    # every scrape names the device it measured and where each engine's
    # weights are
    engines = ([r.engine for r in replica_set.replicas]
               if replica_set is not None else [engine])
    prov.update({
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__,
        "param_devices": "|".join(f"r{i}:{device_ids(e.params)}"
                                  for i, e in enumerate(engines)),
    })
    prov.update({
        "model": name,
        "max_batch": args.maxBatch,
        "max_wait_ms": args.maxWaitMs,
        "max_queue": args.maxQueue,
        "deadline_ms": args.deadlineMs if args.deadlineMs else "none",
        "shed_at": args.shedAt,
        "reqtrace": "on" if reqtracer is not None else "off",
    })
    if quant_info is not None:
        # measured quality guardrail (ISSUE 17): greedy agreement vs the
        # f32 tree and worst-case logit error, pinned into every scrape
        prov["quant_agreement"] = round(float(quant_info["agreement"]), 4)
        prov["quant_logit_max_err"] = round(
            float(quant_info["logit_max_err"]), 6)
    if strategy:
        import jax
        # multi-chip topology provenance (ISSUE 16): every /metrics
        # scrape and bench record names the serving shape it measured
        prov["strategy"] = strategy
        prov["serving_replicas"] = n_replicas
        prov["serving_tp"] = tp_k
        prov["n_devices"] = len(jax.devices())
    if reqtracer is not None:
        prov["slo"] = args.slo if args.slo else "none"
        if reqtracer.access_log is not None:
            prov["access_log"] = reqtracer.access_log.path
            prov["access_log_sample"] = args.logSample
    if decoder is not None:
        prov["decode_slots"] = args.slots
        prov["prompt_buckets"] = ",".join(
            str(b) for b in decoder.prompt_buckets)
        # which prefill buckets attend through a compiled Pallas kernel
        # (none off-TPU, where the kernels would run interpreted)
        prov["prefill_kernels"] = ";".join(
            f"{k}@{','.join(map(str, bs))}" for k, bs in
            sorted(decoder.prefill_mosaic_kernels().items())) or "none"
        prov["speculate"] = args.speculate
        prov["draft_dims"] = args.draftDims or (
            "self" if args.speculate > 0 else "none")
        prov["kv_page_tokens"] = decoder.page_tokens or "dense"
        prov["prefix_cache"] = int(bool(args.prefixCache))
        if args.speculate > 0:
            # measured, resolved per scrape: tokens emitted per target
            # verify dispatch (the ISSUE 14 acceptance number; replica
            # 0's labelled series under dp)
            g = metrics.gauge("spec_accepted_tokens_per_step",
                              "tokens emitted per target verify step",
                              labels=({"replica": "0"}
                                      if replica_set is not None
                                      else None))
            prov["spec_accepted_tokens_per_step"] = \
                lambda: round(g.value, 4)
    if getattr(args, "faultPlan", None):
        prov["fault_plan"] = args.faultPlan
    metrics.set_provenance(prov)

    version = getattr(args, "modelVersion", None) or "v0"
    if replica_set is not None:
        app = ServingApp(name=name, metrics=metrics,
                         replicas=replica_set,
                         request_timeout_s=args.timeout,
                         default_deadline_ms=args.deadlineMs,
                         shed_generate_frac=args.shedAt,
                         version=version)
    else:
        app = ServingApp(name=name, metrics=metrics, engine=engine,
                         batcher=batcher, decoder=decoder,
                         request_timeout_s=args.timeout,
                         default_deadline_ms=args.deadlineMs,
                         shed_generate_frac=args.shedAt,
                         watchdog=watchdog, version=version)
    # resolved per scrape: a rolling weight swap (ISSUE 20) bumps
    # app.model_version and every later scrape names the NEW weights
    prov["model_version"] = lambda: app.model_version
    metrics.set_provenance(prov)
    return app, engine, in_shape, in_dtype


def main(argv=None):
    common.setup_logging()
    import sys
    raw_argv = list(sys.argv[1:]) if argv is None else list(argv)
    args = build_parser().parse_args(raw_argv)
    if getattr(args, "fleet", 0):
        # --fleet K (ISSUE 20): this process becomes the ROUTER — it
        # never initializes jax; each worker re-enters the serve stack
        # in its own process with the router-owned flags stripped
        from bigdl_tpu.serving.fleet.router import run_fleet
        return run_fleet(args, raw_argv)
    common.apply_platform(args)  # --convLayout/--convGeom/--autotune

    from bigdl_tpu.serving import run_server

    app, engine, in_shape, in_dtype = build_app(args)
    if not args.no_warmup:
        engines = ([r.engine for r in app.replicas.replicas]
                   if app.replicas is not None else [engine])
        print(f"warmup: compiling buckets {engine.buckets} at "
              f"{tuple(in_shape)} {in_dtype.__name__}"
              + (f" x{len(engines)} replicas" if len(engines) > 1
                 else ""), flush=True)
        for e in engines:
            e.warmup(in_shape, in_dtype)
    return run_server(app, args.host, args.port)


if __name__ == "__main__":
    raise SystemExit(main())
