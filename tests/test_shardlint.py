"""shardlint (ISSUE 19): positive + negative cases per rule group on
hand-built sharded jaxprs (AbstractMesh — zero devices committed) AND
real perf-zoo models over virtual meshes, plus the flagship zero-error
regression pin, the serving-unsharded-matmul alias contract, the
ResolvedConfig spine, and CLI smoke for the composed `lint` command."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AbstractMesh, Mesh, NamedSharding,
                          PartitionSpec as P)

from bigdl_tpu.analysis import (CATALOG, SHARD_CATALOG,
                                run_kv_sharding_rules,
                                run_replicated_operand_rules,
                                run_sharding_rules,
                                trace_sharded_train_step)
from bigdl_tpu.parallel.grad_comm import make_config

AM = AbstractMesh((2, 4), ("data", "model"))
BIG = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB


def _trace(fn, *args, in_shardings=None):
    f = jax.jit(fn, in_shardings=in_shardings)
    return jax.make_jaxpr(f)(*args)


def errors(rep, rule=None):
    return [f for f in rep.findings if f.severity == "error"
            and (rule is None or f.rule == rule)]


# ------------------------------------------------------------- catalog
def test_shard_catalog_merged_into_main_catalog():
    for rule, (fam, sev, desc) in SHARD_CATALOG.items():
        assert rule in CATALOG, rule
        assert fam == "sharding", rule
        assert sev in ("error", "warning"), rule
        assert desc, rule


# ============================== group 1: strategy/collective consistency
def test_undeclared_axis_in_constraint_is_error():
    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("model", None))) * 2.0
    rep = run_sharding_rules(_trace(f, BIG), mesh_axes={"data": 2},
                             strategy="dp")
    hits = errors(rep, "shard-collective-axis")
    assert hits and "model" in str(hits[0].detail["axes"])


def test_declared_axis_in_constraint_is_clean():
    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("model", None))) * 2.0
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="tp")
    assert not errors(rep, "shard-collective-axis")


def test_unreferenced_mesh_axis_is_missing_signature():
    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("data", None))) * 2.0
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="tp")
    hits = errors(rep, "shard-collective-missing")
    assert hits and any(h.detail.get("axis") == "model" for h in hits)


def test_grad_compress_with_no_16bit_bucket_is_missing():
    gc = make_config("bf16", "auto")
    assert gc.active

    def f(x):  # f32 constraint only — the compressed path never engaged
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("data", "model"))) * 2.0
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="dp", grad_comm=gc)
    hits = errors(rep, "shard-collective-missing")
    assert any(h.where == "grad_comm" for h in hits)


def test_grad_compress_with_bf16_bucket_is_clean():
    gc = make_config("bf16", "auto")

    def f(x):
        b = jax.lax.with_sharding_constraint(
            x.astype(jnp.bfloat16), NamedSharding(AM, P()))
        return jax.lax.with_sharding_constraint(
            x * 1.5, NamedSharding(AM, P("data", "model"))) \
            + b.astype(jnp.float32)
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="dp", grad_comm=gc)
    assert not any(h.where == "grad_comm"
                   for h in errors(rep, "shard-collective-missing"))


def test_explicit_collective_outside_strategy_is_extra():
    # shard_map graphs are the only place explicit collectives appear;
    # conftest pins 8 host devices so a real 2x4 mesh exists
    shard_map = jax.shard_map
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    g = shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                  in_specs=P("data", None), out_specs=P("data", None))
    closed = jax.make_jaxpr(g)(jax.ShapeDtypeStruct((8, 512),
                                                    jnp.float32))
    rep = run_sharding_rules(closed, mesh_axes={"data": 2, "model": 4},
                             strategy="dp")
    assert errors(rep, "shard-collective-extra")
    # the same psum is legitimate under tp (model is an expected axis)
    rep2 = run_sharding_rules(closed, mesh_axes={"data": 2, "model": 4},
                              strategy="tp")
    assert not errors(rep2, "shard-collective-extra")


# ======================================= group 3: wire dtype and remat
def test_f32_replication_point_under_grad_compress_is_error():
    gc = make_config("bf16", "auto")

    def f(x):
        b = jax.lax.with_sharding_constraint(  # satisfies signature (b)
            x[:1].astype(jnp.bfloat16), NamedSharding(AM, P()))
        big = jax.lax.with_sharding_constraint(  # 4 MiB f32 on the wire
            x * 2.0, NamedSharding(AM, P()))
        return big + b.astype(jnp.float32)
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="dp", grad_comm=gc)
    hits = errors(rep, "shard-wire-dtype")
    assert hits and hits[0].detail["compress"] == "bf16"


def test_wire_dtype_silent_without_grad_compress():
    def f(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(AM, P()))
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4},
                             strategy="dp", grad_comm=None)
    assert not rep.by_rule("shard-wire-dtype")


def test_quant_remat_before_boundary_is_warning():
    q = jax.ShapeDtypeStruct((1024, 1024), jnp.int8)

    def f(w):
        dense = w.astype(jnp.float32) * 0.02  # 4 MiB rematerialized
        return jax.lax.with_sharding_constraint(
            dense, NamedSharding(AM, P()))
    rep = run_sharding_rules(_trace(f, q),
                             mesh_axes={"data": 2, "model": 4})
    hits = rep.by_rule("shard-quant-remat-wire")
    assert hits and hits[0].severity == "warning"
    assert hits[0].detail["src_dtype"] == "int8"


def test_quant_kept_8bit_across_boundary_is_clean():
    q = jax.ShapeDtypeStruct((1024, 1024), jnp.int8)

    def f(w):
        w8 = jax.lax.with_sharding_constraint(
            w, NamedSharding(AM, P(None, "model")))
        return w8.astype(jnp.float32) * 0.02  # dequant AFTER the wire
    rep = run_sharding_rules(_trace(f, q),
                             mesh_axes={"data": 2, "model": 4})
    assert not rep.by_rule("shard-quant-remat-wire")


# ============================================== group 4: reshard churn
def test_conflicting_consecutive_constraints_are_churn():
    def f(x):
        a = jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("model", None)))
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(AM, P(None, "model")))
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4})
    hits = rep.by_rule("shard-reshard-churn")
    assert hits and hits[0].severity == "warning"
    assert hits[0].detail["wasted_bytes"] > 0


def test_stable_layout_is_not_churn():
    def f(x):
        a = jax.lax.with_sharding_constraint(
            x, NamedSharding(AM, P("model", None)))
        return jax.lax.with_sharding_constraint(
            a * 2.0, NamedSharding(AM, P("model", None)))
    rep = run_sharding_rules(_trace(f, BIG),
                             mesh_axes={"data": 2, "model": 4})
    assert not rep.by_rule("shard-reshard-churn")


# ======================================= group 2: replicated operands
def _abstract_params():
    return {"emb": {"w": jax.ShapeDtypeStruct((4096, 512), jnp.float32)},
            "bias": jax.ShapeDtypeStruct((512,), jnp.float32)}


def test_replicated_big_operand_under_model_axis_is_error():
    specs = {"emb": {"w": P()}, "bias": P()}
    rep = run_replicated_operand_rules(_abstract_params(),
                                       {"data": 2, "model": 4},
                                       specs=specs)
    hits = errors(rep, "shard-replicated-operand")
    assert len(hits) == 1  # the 1-D bias never fires
    assert "emb" in hits[0].where
    assert "model" in hits[0].detail["splittable_axes"]


def test_split_spec_is_clean_and_data_axis_never_fires():
    specs = {"emb": {"w": P(None, "model")}, "bias": P()}
    rep = run_replicated_operand_rules(_abstract_params(),
                                       {"data": 2, "model": 4},
                                       specs=specs)
    assert not rep.findings
    # a pure-dp mesh replicates params BY DESIGN
    rep2 = run_replicated_operand_rules(
        _abstract_params(), {"data": 8},
        specs={"emb": {"w": P()}, "bias": P()})
    assert not rep2.findings


def test_unknown_placement_never_fires():
    # abstract leaves with no spec tree and no committed sharding:
    # placement is unknown, not replicated
    rep = run_replicated_operand_rules(_abstract_params(),
                                       {"data": 2, "model": 4})
    assert not rep.findings


def test_legacy_alias_keeps_pr15_serving_output():
    # the serving-unsharded-matmul spelling only reads PLACED trees and
    # emits the PR 15 finding shape (family serving, tp in detail)
    rep = run_replicated_operand_rules(
        _abstract_params(), {"model": 4}, split_axes=("model",),
        rule_id="serving-unsharded-matmul")
    assert not rep.findings  # abstract tree: placed-only semantics
    placed = {"w": jnp.zeros((1024, 512), jnp.float32)}  # 2 MiB, 1 dev
    rep2 = run_replicated_operand_rules(
        placed, {"model": 4}, split_axes=("model",),
        rule_id="serving-unsharded-matmul")
    hits = rep2.by_rule("serving-unsharded-matmul")
    assert hits and hits[0].family == "serving"
    assert hits[0].detail["tp"] == 4


# ============================================ group 5: KV pool misfit
def _kv_leaf(kv_heads, dtype=jnp.bfloat16):
    # (pool_pages, kv_heads, page_tokens, head_dim) ~ several MiB
    return jax.ShapeDtypeStruct((33, kv_heads, 128, 64), dtype)


def test_kv_heads_not_divisible_by_tp_is_misfit():
    rep = run_kv_sharding_rules({"k": _kv_leaf(6), "v": _kv_leaf(6)},
                                4, page_tokens=128)
    hits = errors(rep, "kv-shard-misfit")
    assert len(hits) == 2
    assert hits[0].detail["kv_heads"] == 6 and hits[0].detail["tp"] == 4


def test_kv_heads_divisible_is_clean_and_tp1_silent():
    rep = run_kv_sharding_rules({"k": _kv_leaf(8), "v": _kv_leaf(8)}, 4)
    assert not rep.findings
    rep2 = run_kv_sharding_rules({"k": _kv_leaf(6)}, 1)
    assert not rep2.findings


# =============================== real models over virtual meshes
def _lm():
    from bigdl_tpu.cli.perf import build_model
    return build_model("transformer_lm", class_num=1000,
                       lm_attn_impl="flash")


def test_flagship_tp_grad_compress_is_zero_errors():
    # the regression pin: transformer_lm tp:2 + bf16 compression is the
    # blessed multichip config and must stay shardlint-clean
    model, in_shape = _lm()
    closed, meta = trace_sharded_train_step(
        model, in_shape, 8, mesh_axes={"data": 2, "model": 2},
        is_lm=True, grad_comm=make_config("bf16", "auto"))
    rep = run_sharding_rules(closed, mesh_axes=meta["mesh_axes"],
                             strategy="tp",
                             grad_comm=make_config("bf16", "auto"),
                             param_specs=meta["param_specs"],
                             params=meta["params"])
    assert not errors(rep), [f.render() for f in errors(rep)[:3]]


def test_missharded_tp3_fires_multiple_groups():
    # 512 % 3 != 0: megatron falls back to full replication — the
    # strategy is a silent no-op AND every big weight replicates
    model, in_shape = _lm()
    closed, meta = trace_sharded_train_step(
        model, in_shape, 8, mesh_axes={"data": 2, "model": 3},
        is_lm=True)
    rep = run_sharding_rules(closed, mesh_axes=meta["mesh_axes"],
                             strategy="tp",
                             param_specs=meta["param_specs"],
                             params=meta["params"])
    rules = {f.rule for f in errors(rep)}
    assert "shard-collective-missing" in rules
    assert "shard-replicated-operand" in rules


# ------------------------------------------------- ResolvedConfig spine
def test_resolve_lint_config_virtual_mesh_and_grad_comm():
    import argparse

    from bigdl_tpu.cli.common import resolve_lint_config
    args = argparse.Namespace(model="transformer_lm", batchSize=8,
                              strategy="tp:4", gradCompress="bf16+ec",
                              gradBuckets="auto", quantize="int8+kv8",
                              speculate=4, kvPageTokens="auto")
    cfg = resolve_lint_config(args)
    assert cfg.mesh == {"data": 2, "model": 4}
    assert cfg.strategy == "tp" and cfg.strategy_k == 4
    assert cfg.make_grad_comm().active
    assert cfg.kv_page_tokens is None  # 'auto' is serve-side only
    assert cfg.describe()["mesh"] == "data:2,model:4"


def test_strategy_lint_spec_metadata():
    from bigdl_tpu.parallel import DataParallel, TensorParallel
    from bigdl_tpu.parallel.mesh import local_mesh
    dp = DataParallel(local_mesh("data"))
    meta = dp.lint_spec_metadata()
    assert meta["strategy"] == "dp" and "data" in meta["mesh_axes"]

    model, _ = _lm()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    tp = TensorParallel(mesh, model)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    meta = tp.lint_spec_metadata(params)
    leaves = jax.tree_util.tree_leaves(
        meta["param_specs"], is_leaf=lambda x: isinstance(x, P))
    assert any(not all(a is None for a in tuple(sp))
               for sp in leaves if isinstance(sp, P))


# ------------------------------------------------------------ CLI smoke
@pytest.mark.slow
def test_cli_flagship_composed_config_is_clean():
    from bigdl_tpu.cli.lint import main
    rc = main(["transformer_lm", "--strategy", "tp:2",
               "--gradCompress", "bf16", "--quantize", "int8+kv8",
               "--strict"])
    assert rc == 0


@pytest.mark.slow
def test_cli_missharded_config_exits_2_under_strict(capsys):
    from bigdl_tpu.cli.lint import main
    rc = main(["transformer_lm", "--strategy", "tp:3", "--strict"])
    assert rc == 2
    out = capsys.readouterr().out
    assert "shard-" in out


def test_serve_lint_strict_dp_tp_stamps_lint_mesh():
    # ISSUE 19 satellite bugfix: `serve --lint=strict` under dp:N+tp:K
    # lints ONCE on the first replica's tp group (every replica compiles
    # the identical graph) and records the mesh it vetted in provenance
    import json as _json

    from bigdl_tpu.cli import common, serve as serve_cli
    args = serve_cli.build_parser().parse_args(
        ["transformer_lm", "--randomInit", "--vocabSize", "50",
         "--dModel", "32", "--numLayers", "2", "--numHeads", "2",
         "--seq", "64", "--slots", "2", "--buckets", "1,2",
         "--maxWaitMs", "2", "--strategy", "dp:2+tp:2",
         "--lint=strict"])
    common.apply_platform(args)
    app, eng, in_shape, in_dtype = serve_cli.build_app(args)
    try:
        page = app.metrics.render()
        prov = _json.loads(
            [l for l in page.splitlines()
             if l.startswith("# provenance ")][0][len("# provenance "):])
        assert prov["lint_mesh"] == "model:2 x 2 replica(s)"
        assert prov["strategy"] == "dp:2+tp:2"
    finally:
        app.close()
