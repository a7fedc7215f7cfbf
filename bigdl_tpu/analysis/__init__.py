"""tpulint — trace-time static analysis for TPU perf/correctness
anti-patterns (ISSUE 4 tentpole).

BigDL's operability came from catching config mistakes at submit time,
before a cluster burned hours (PAPER §BigDL). The TPU analogue: trace a
model's full train step with ``jax.make_jaxpr`` under **abstract**
inputs (no compilation, no device, seconds on CPU), walk every nested
jit/custom_vjp/pallas_call sub-jaxpr, and evaluate a rule registry over
the jaxpr plus the kernel/block/layout metadata PRs 1–3 already record.
The same pass is the CI gate that keeps those PRs' wins from regressing.

Public surface:

* :func:`lint_fn` — lint any callable (traced with the given abstract
  args); jaxpr rules only.
* :func:`lint_perf_model` — lint a perf-zoo model end-to-end: builds the
  model (LMs get the flash kernel forced on so the TPU-projected trace
  is analyzed even off-chip), constructs the donated SGD train step the
  perf harness runs, traces it abstractly, and evaluates jaxpr + module
  rules. The ``bigdl-tpu lint`` CLI and the perf ``--lint`` pre-flight
  call this.
* :func:`preflight_optimizer` — lint a built
  :class:`~bigdl_tpu.optim.Optimizer` before ``optimize()`` (the
  training CLIs' ``--lint`` flag): module rules always; the real
  ``_build_step`` product is traced when the dataset exposes its batch
  geometry without consuming the shuffle stream.

Findings: :class:`~bigdl_tpu.analysis.report.Finding` /
:class:`~bigdl_tpu.analysis.report.Report`; the rule catalog with
severities lives in :data:`bigdl_tpu.analysis.rules.CATALOG`
(documented in PERF.md §12).
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu.analysis.report import Finding, Report, SEVERITIES
from bigdl_tpu.analysis.rules import (CATALOG, assert_blocks_tileable,
                                      check_block_padding,
                                      check_block_tiling, min_sublane,
                                      run_comm_rules, run_decode_rules,
                                      run_jaxpr_rules,
                                      run_memory_rules, run_module_rules,
                                      run_serving_tp_rules)
from bigdl_tpu.analysis.sharding_rules import (SHARD_CATALOG,
                                               run_kv_sharding_rules,
                                               run_replicated_operand_rules,
                                               run_sharding_rules)

__all__ = ["Finding", "Report", "SEVERITIES", "CATALOG", "SHARD_CATALOG",
           "check_block_tiling", "check_block_padding",
           "assert_blocks_tileable", "min_sublane",
           "run_jaxpr_rules", "run_module_rules", "run_comm_rules",
           "run_memory_rules", "run_decode_rules",
           "run_serving_tp_rules", "run_sharding_rules",
           "run_replicated_operand_rules", "run_kv_sharding_rules",
           "lint_fn", "trace_train_step", "trace_sharded_train_step",
           "lint_perf_model", "lint_config",
           "preflight_optimizer"]


def lint_fn(fn, *args, report: Optional[Report] = None, **kwargs) -> Report:
    """Trace ``fn(*args, **kwargs)`` abstractly (args may be arrays or
    ``jax.ShapeDtypeStruct``) and run every jaxpr rule. Pass an already-
    jitted ``fn`` to get donation analysis of its jit boundary."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return run_jaxpr_rules(closed, report)


def trace_train_step(model, in_shape, batch, *, dtype=None, is_lm=False,
                     vocab: int = 32000, donate=(0, 1, 2)):
    """ClosedJaxpr of the canonical SGD train step over ``model`` at
    ``batch`` x ``in_shape`` — the same step shape the perf harness
    compiles (donated (params, mod_state, opt_state), bf16 activations
    by default, fp32 loss). Everything abstract: params/opt-state come
    from ``jax.eval_shape``, inputs are ShapeDtypeStructs; nothing is
    allocated or executed."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD

    dtype = jnp.bfloat16 if dtype is None else dtype
    crit = (nn.TimeDistributedCriterion(nn.ClassNLLCriterion()) if is_lm
            else nn.ClassNLLCriterion())
    opt = SGD(learning_rate=0.01, momentum=0.9)

    if is_lm:
        if dtype == jnp.bfloat16:
            model.compute_dtype = dtype  # cast lives after the embedding
        x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
        y = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
    else:
        x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.float32)
        y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    mod_state = model.init_state()
    opt_state = jax.eval_shape(opt.init, params)

    def train_step(params, mod_state, opt_state, x, y, rng):
        def loss_fn(p):
            xc = (x.astype(dtype)
                  if jnp.issubdtype(x.dtype, jnp.floating) else x)
            out, ms = model.apply(p, mod_state, xc, training=True, rng=rng)
            return crit(out.astype(jnp.float32), y), ms

        (loss, ms), grads = jax.value_and_grad(loss_fn,
                                               has_aux=True)(params)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, ms, new_o, loss

    step = (jax.jit(train_step, donate_argnums=donate) if donate
            else jax.jit(train_step))
    return jax.make_jaxpr(step)(params, mod_state, opt_state, x, y, key)


def trace_sharded_train_step(model, in_shape, batch, *, mesh_axes,
                             dtype=None, is_lm=False, grad_comm=None,
                             donate=(0, 1, 2)):
    """ClosedJaxpr of the SHARDED SGD train step over ``model`` on the
    declared ``mesh_axes`` (axis -> size), plus the metadata shardlint
    needs: ``(closed, {"param_specs", "mesh_axes", "params"})``.

    The mesh is a :class:`jax.sharding.AbstractMesh` — annotations only,
    zero real devices, no compile, so a 32-chip layout lints on a 1-CPU
    box (the ISSUE 19 contract). The layout mirrors what the real
    strategies build: Megatron param specs when a ``model`` axis > 1
    (:func:`~bigdl_tpu.parallel.tensor_parallel.megatron_specs`, with
    its divisibility fallbacks — so a mis-fitting tp degree shows up
    here exactly as it would on chips), replicated params otherwise,
    batch sharded over ``data`` (and ``seq`` when declared), and the
    compressed-bucket grad path when ``grad_comm`` is active."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel.tensor_parallel import (megatron_specs,
                                                    replicated_specs)

    axes = {str(k): int(v) for k, v in dict(mesh_axes).items()}
    mesh = AbstractMesh(tuple(axes.values()), tuple(axes))
    dtype = jnp.bfloat16 if dtype is None else dtype
    crit = (nn.TimeDistributedCriterion(nn.ClassNLLCriterion()) if is_lm
            else nn.ClassNLLCriterion())
    opt = SGD(learning_rate=0.01, momentum=0.9)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    mod_state = model.init_state()
    opt_state = jax.eval_shape(opt.init, params)

    if axes.get("model", 1) > 1:
        specs = megatron_specs(model, params, "model", axes["model"])
    else:
        specs = replicated_specs(params)
    is_spec = lambda s: isinstance(s, P)
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=is_spec)
    o_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), opt_state)
    seq_axis = "seq" if (is_lm and axes.get("seq", 1) > 1) else None
    if is_lm:
        if dtype == jnp.bfloat16:
            model.compute_dtype = dtype
        x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
        y = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
        x_sh = y_sh = NamedSharding(mesh, P("data", seq_axis))
    else:
        x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.float32)
        y = jax.ShapeDtypeStruct((batch,), jnp.int32)
        x_sh = NamedSharding(mesh, P("data"))
        y_sh = NamedSharding(mesh, P("data"))

    def train_step(params, mod_state, opt_state, x, y, rng):
        def loss_fn(p):
            xc = (x.astype(dtype)
                  if jnp.issubdtype(x.dtype, jnp.floating) else x)
            out, ms = model.apply(p, mod_state, xc, training=True, rng=rng)
            return crit(out.astype(jnp.float32), y), ms

        (loss, ms), grads = jax.value_and_grad(loss_fn,
                                               has_aux=True)(params)
        if grad_comm is not None and getattr(grad_comm, "active", False):
            from bigdl_tpu.parallel.grad_comm import apply_grad_comm
            grads, _ = apply_grad_comm(grads, grad_comm, mesh)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, ms, new_o, loss

    step = jax.jit(train_step,
                   in_shardings=(p_sh, None, o_sh, x_sh, y_sh, None),
                   donate_argnums=donate or ())
    closed = jax.make_jaxpr(step)(params, mod_state, opt_state, x, y, key)
    return closed, {"param_specs": specs, "mesh_axes": axes,
                    "params": params}


def _bn_fallback_rule(model, closed, report: Report) -> None:
    """Model+jaxpr combo rule: fused BN was requested, eligible sites
    exist, but fewer forward kernels were traced than sites — some (or
    all) silently fell back to the jnp path (rows untileable at this
    batch)."""
    from bigdl_tpu.analysis.jaxpr_walk import (iter_levels,
                                               pallas_kernel_name)
    from bigdl_tpu.nn.norm import BatchNormalization

    sites = [m for m in model.modules()
             if isinstance(m, BatchNormalization) and m.fused
             and m.affine and m.axis_name is None and not m.stat_sample
             and int(m.n_output) % 128 == 0]
    if not sites:
        return
    fwd_names = {"_fba_fwd_kernel", "_stats_kernel"}
    traced = 0
    for lv in iter_levels(closed):
        for eqn in lv.jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" \
                    and pallas_kernel_name(eqn) in fwd_names:
                traced += 1
    if traced < len(sites):
        report.add(Finding(
            rule="tile-bn-fallback", family="tiling",
            severity="warning",
            message=(f"fused BN requested on {len(sites)} eligible "
                     f"site(s) but only {traced} fused stats/apply "
                     "kernel(s) traced — the rest fell back to the jnp "
                     "path (rows % row-block != 0 at this batch)"),
            hint="--autotune measure can unlock smaller legal row "
                 "blocks; or pick a batch whose rows tile",
            detail={"eligible_sites": len(sites),
                    "traced_kernels": traced}))


def lint_perf_model(name: str, batch: int = 32, *, seq_len=None,
                    dtype=None, fused_bn=None, classes: int = 1000,
                    trace: bool = True, strategy=None,
                    grad_compress=None) -> Report:
    """Full lint of one perf-zoo model (see module docstring). LMs are
    built with ``attn_impl='flash'`` forced so the TPU-projected kernels
    appear in the CPU trace; ``trace=False`` skips the jaxpr pass
    (module rules only — used when only configuration is in question).
    ``strategy``/``grad_compress`` are the perf CLI's spec strings; when
    a multi-device strategy is requested the gradient-communication
    rules run over the abstract param tree (PERF.md §17)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.cli.common import apply_fused_bn
    from bigdl_tpu.cli.perf import build_model

    dtype = jnp.bfloat16 if dtype is None else dtype
    model, in_shape = build_model(name, class_num=classes,
                                  seq_len=seq_len, lm_attn_impl="flash")
    apply_fused_bn(model, fused_bn)
    is_lm = name.startswith("transformer_lm")
    seq = in_shape[0] if is_lm else None

    report = Report()
    dtname = jnp.dtype(dtype).name
    run_module_rules(model, report, seq=seq, dtype=dtname)
    if strategy is not None:
        from bigdl_tpu.cli.common import parse_strategy_spec

        strat_name, _ = parse_strategy_spec(strategy)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        run_comm_rules(params, strat_name, grad_compress, report)
    if trace:
        closed = trace_train_step(model, in_shape, batch, dtype=dtype,
                                  is_lm=is_lm)
        run_jaxpr_rules(closed, report)
        _bn_fallback_rule(model, closed, report)
    # HBM working-set rule (ISSUE 12): abstract plan over the same
    # state pytrees the perf step would hold — argument-side categories
    # only (no compilation), so "plan exceeds HBM" fires pre-compile
    try:
        from bigdl_tpu.obs import memory
        from bigdl_tpu.optim import SGD

        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(
            SGD(learning_rate=0.01, momentum=0.9).init, params)
        if is_lm:
            x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
            y = jax.ShapeDtypeStruct((batch, *in_shape), jnp.int32)
        else:
            x = jax.ShapeDtypeStruct((batch, *in_shape), jnp.float32)
            y = jax.ShapeDtypeStruct((batch,), jnp.int32)
        plan = memory.build_plan(params=params, opt_state=opt_state,
                                 batch=(x, y), batch_size=batch,
                                 model_name=name)
        run_memory_rules(plan, report)
    except Exception as e:
        report.add(Finding(
            rule="lint-trace-error", family="meta", severity="info",
            message=f"memory rules skipped ({type(e).__name__}: {e})",
            hint="the jaxpr/module rules still ran"))
    return report


def lint_config(cfg) -> Report:
    """Lint everything one resolved run configuration would execute
    (ISSUE 19): the single-device pass (:func:`lint_perf_model`), the
    SHARDED train step when ``--strategy`` declares a mesh (shardlint
    rules over an :class:`~jax.sharding.AbstractMesh` trace — zero real
    devices), and the serving decode surface when ``--quantize`` /
    ``--speculate`` / ``--kvPageTokens`` ask for one. ``cfg`` is a
    :class:`bigdl_tpu.cli.common.ResolvedConfig` — the one object the
    lint CLI and every preflight hand over (the ResolvedConfig
    spine)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.cli.common import apply_fused_bn
    from bigdl_tpu.cli.perf import build_model

    dtype = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    strat_spec = None
    if cfg.strategy:
        strat_spec = (f"{cfg.strategy}:{cfg.strategy_k}"
                      if cfg.strategy_k else cfg.strategy)
    report = lint_perf_model(cfg.model, cfg.batch, seq_len=cfg.seq,
                             dtype=dtype, fused_bn=cfg.fused_bn,
                             classes=cfg.classes, trace=cfg.trace,
                             strategy=strat_spec,
                             grad_compress=cfg.grad_compress)
    mesh = cfg.mesh
    is_lm = cfg.model.startswith("transformer_lm")
    grad_comm = cfg.make_grad_comm()

    # ------------------------------------------- sharded training step
    if cfg.trace and mesh and cfg.strategy in ("dp", "tp", "sp"):
        model, in_shape = build_model(cfg.model, class_num=cfg.classes,
                                      seq_len=cfg.seq,
                                      lm_attn_impl="flash")
        apply_fused_bn(model, cfg.fused_bn)
        try:
            closed, meta = trace_sharded_train_step(
                model, in_shape, cfg.batch, mesh_axes=mesh, dtype=dtype,
                is_lm=is_lm, grad_comm=grad_comm)
        except Exception as e:
            report.add(Finding(
                rule="lint-trace-error", family="meta", severity="info",
                message=f"sharded step trace skipped "
                        f"({type(e).__name__}: {e})",
                hint="the single-device passes still ran"))
        else:
            run_sharding_rules(closed, mesh_axes=meta["mesh_axes"],
                               strategy=cfg.strategy,
                               grad_comm=grad_comm,
                               param_specs=meta["param_specs"],
                               params=meta["params"], context="train",
                               report=report)
    elif cfg.strategy in ("pp", "ep"):
        report.add(Finding(
            rule="lint-trace-error", family="meta", severity="info",
            message=f"--strategy {cfg.strategy}: the staged/expert step "
                    "composes inside the perf harness; shardlint traces "
                    "dp/tp/sp step graphs",
            hint="the config-level comm rules above still apply"))

    # ------------------------------------------- serving decode surface
    wants_serving = bool(cfg.quantize or cfg.speculate
                         or cfg.kv_page_tokens)
    if cfg.trace and wants_serving:
        if not is_lm:
            report.add(Finding(
                rule="lint-trace-error", family="meta", severity="info",
                message="--quantize/--speculate/--kvPageTokens describe "
                        "the LM serving surface; skipped for "
                        f"{cfg.model}",
                hint="lint a transformer_lm* model to cover decode"))
        else:
            tp_k = int(mesh.get("model", 1)) if cfg.strategy == "tp" \
                else 1
            try:
                from bigdl_tpu.serving.decode import \
                    abstract_decode_engine
                smodel, _ = build_model(cfg.model, class_num=cfg.classes,
                                        seq_len=cfg.seq,
                                        lm_attn_impl="flash")
                kvp = cfg.kv_page_tokens
                if cfg.quantize and "kv8" in cfg.quantize and not kvp:
                    # kv8 is a page-pool layout (mirrors serve's pick)
                    for cand in (128, 64, 32, 256):
                        if smodel.max_len % cand == 0:
                            kvp = cand
                            break
                eng = abstract_decode_engine(
                    smodel, slots=cfg.slots, kv_page_tokens=kvp,
                    speculate=cfg.speculate, tp=tp_k,
                    quantize=cfg.quantize)
                closed = eng.trace_step_jaxpr()
            except Exception as e:
                report.add(Finding(
                    rule="lint-trace-error", family="meta",
                    severity="info",
                    message=f"serving decode trace skipped "
                            f"({type(e).__name__}: {e})",
                    hint="the training-side passes still ran"))
            else:
                head_dim = getattr(
                    smodel.encoder._modules[0].mha, "head_dim",
                    smodel.d_model // 4)
                run_decode_rules(closed, page_tokens=kvp,
                                 max_len=eng.max_len, head_dim=head_dim,
                                 dtype=eng.cache_dtype, report=report)
                if tp_k > 1:
                    run_sharding_rules(closed,
                                       mesh_axes={"model": tp_k},
                                       strategy=None, context="serving",
                                       report=report)
                    run_kv_sharding_rules(
                        eng._kv.pools if eng.paged else eng._cache,
                        tp_k, page_tokens=kvp, report=report)
                    # replicated-operand over the serving layout the
                    # engine would commit (abstract: specs, not arrays)
                    raw = jax.eval_shape(smodel.init,
                                         jax.random.PRNGKey(0))
                    specs = eng._shard.param_specs(smodel, raw)
                    run_replicated_operand_rules(
                        raw, {"model": tp_k}, specs=specs,
                        report=report)
    return report


def preflight_optimizer(opt) -> Report:
    """Lint a built Optimizer before it trains (the training CLIs'
    ``--lint`` pre-flight). Module rules always run; the jaxpr pass runs
    when the step can be traced without side effects: single-device
    strategy and a dataset exposing ``features``/``labels``/
    ``batch_size`` (reading them, unlike pulling a batch, does not
    advance the shuffle RNG that step-equivalent resume depends on)."""
    import numpy as np

    report = Report()
    dtname = ("bfloat16" if getattr(opt, "compute_dtype", None) is not None
              else "float32")
    run_module_rules(opt.model, report, dtype=dtname)

    strat_name = None
    if opt.strategy is not None:
        try:
            import jax

            from bigdl_tpu.parallel import DataParallel, TensorParallel

            if isinstance(opt.strategy, TensorParallel):
                strat_name = "tp"
            elif isinstance(opt.strategy, DataParallel):
                strat_name = "dp"
            cfg = getattr(opt.strategy, "grad_comm", None)
            compress = cfg.compress if cfg is not None else None
            params = jax.eval_shape(opt.model.init, jax.random.PRNGKey(0))
            run_comm_rules(params, strat_name, compress, report)
        except Exception as e:
            report.add(Finding(
                rule="lint-trace-error", family="meta", severity="info",
                message=f"comm rules skipped ({type(e).__name__}: {e})",
                hint="module-level rules still ran"))

    ds = opt.dataset
    feats = getattr(ds, "features", None)
    labs = getattr(ds, "labels", None)
    bs = getattr(ds, "batch_size", None)
    if opt.strategy is not None:
        # shardlint (ISSUE 19): the SHARDED step this run would compile,
        # traced over an AbstractMesh clone of the strategy's real mesh —
        # megatron specs + the strategy's grad_comm annotations, no
        # compile, so the multichip preflight stays seconds on CPU
        if strat_name not in ("dp", "tp") or feats is None or not bs:
            return report
        try:
            smeta = opt.strategy.lint_spec_metadata()
            axes = smeta.get("mesh_axes") or {}
            if not axes:
                return report
            import jax.numpy as jnp
            dt = (jnp.bfloat16
                  if getattr(opt, "compute_dtype", None) is not None
                  else jnp.float32)
            closed, meta = trace_sharded_train_step(
                opt.model, tuple(feats.shape[1:]), int(bs),
                mesh_axes=axes, dtype=dt, is_lm=False,
                grad_comm=smeta.get("grad_comm"))
            run_sharding_rules(
                closed, mesh_axes=meta["mesh_axes"],
                strategy=smeta.get("strategy", strat_name),
                grad_comm=smeta.get("grad_comm"),
                param_specs=meta["param_specs"], params=meta["params"],
                context="train", report=report)
        except Exception as e:
            report.add(Finding(
                rule="lint-trace-error", family="meta", severity="info",
                message=f"sharded step trace skipped "
                        f"({type(e).__name__}: {e})",
                hint="module/comm rules still ran"))
        return report
    if feats is None or labs is None or not bs:
        return report
    try:
        import jax

        from bigdl_tpu.ops.conv2d import policy_snapshot, restore_policy

        x = jax.ShapeDtypeStruct((int(bs),) + tuple(feats.shape[1:]),
                                 np.asarray(feats).dtype)
        y = jax.ShapeDtypeStruct((int(bs),) + tuple(labs.shape[1:]),
                                 np.asarray(labs).dtype)
        snap = policy_snapshot()
        try:
            step, _ = opt._build_step()
            key = jax.random.PRNGKey(0)
            params = jax.eval_shape(opt.model.init, key)
            mod_state = opt.model.init_state()
            opt_state = jax.eval_shape(opt.optim_method.init, params)
            closed = jax.make_jaxpr(step)(params, mod_state, opt_state,
                                          x, y, key)
        finally:
            restore_policy(snap)
        run_jaxpr_rules(closed, report)
        _bn_fallback_rule(opt.model, closed, report)
    except Exception as e:  # surface, never block training on lint bugs
        report.add(Finding(
            rule="lint-trace-error", family="meta", severity="info",
            message=f"step trace skipped ({type(e).__name__}: {e})",
            hint="module-level rules still ran"))
    return report
