"""ISSUE 10: compressed, bucketed, overlapped gradient all-reduce.

Covers the tentpole contract end-to-end on the 8-virtual-device CPU
platform (conftest): deterministic bucket layout, bit-exact compression
round-trips, error-compensation exactness and 50-step convergence, dp
final-loss parity with compression on/off through the perf harness,
the grad_comm autotune cache namespace, perf-JSON column stamping, the
comm lint rules, and the CLI flag surface.
"""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu import tuning
from bigdl_tpu.parallel import grad_comm as gc
from bigdl_tpu.parallel.grad_comm import (COMPRESS_MODES,
                                          DEFAULT_BUCKET_BYTES,
                                          GradCommConfig, apply_grad_comm,
                                          build_bucket_plan,
                                          compressed_psum, make_config)
from bigdl_tpu.tuning.cache import AutotuneCache


def _mesh(n=None):
    devs = jax.devices()
    n = len(devs) if n is None else n
    return Mesh(np.array(devs[:n]), ("data",))


def _tree(seed=0):
    rs = np.random.RandomState(seed)
    return {
        "conv": {"w": jnp.asarray(rs.randn(300, 300), jnp.float32),
                 "b": jnp.asarray(rs.randn(300), jnp.float32)},
        "fc": {"w": jnp.asarray(rs.randn(128, 128), jnp.float32)},
        "step": jnp.asarray(7, jnp.int32),  # non-inexact: passthrough
    }


# ----------------------------------------------------------- config surface
class TestConfig:
    def test_parse_and_make(self):
        cfg = make_config("bf16+ec", "auto")
        assert cfg.active and cfg.error_comp
        assert cfg.wire_dtype == "bfloat16"
        cfg = make_config("fp16", "8")
        assert cfg.bucket_bytes == 8 * 2 ** 20 and not cfg.error_comp
        assert make_config("off", "auto") is None

    def test_bad_specs_raise(self):
        with pytest.raises(ValueError):
            make_config("int8", "auto")
        with pytest.raises(ValueError):
            make_config("bf16", "0")
        with pytest.raises(ValueError):
            make_config("bf16", "many")

    def test_cli_choices_mirror_modes(self):
        # cli/common keeps a literal copy so argparse never imports jax
        from bigdl_tpu.cli.common import GRAD_COMPRESS_CHOICES
        assert tuple(GRAD_COMPRESS_CHOICES) == tuple(COMPRESS_MODES)


# ------------------------------------------------------------- bucket plan
class TestBucketPlan:
    def test_layout_is_deterministic(self):
        p1 = build_bucket_plan(_tree(0), DEFAULT_BUCKET_BYTES)
        p2 = build_bucket_plan(_tree(1), DEFAULT_BUCKET_BYTES)
        assert p1.signature == p2.signature  # keyed by structure, not values
        assert [b.leaf_ids for b in p1.buckets] == \
            [b.leaf_ids for b in p2.buckets]

    def test_signature_tracks_bound(self):
        p1 = build_bucket_plan(_tree(), DEFAULT_BUCKET_BYTES)
        p2 = build_bucket_plan(_tree(), 256 * 1024)
        assert p1.signature != p2.signature

    def test_size_bounded_split_and_passthrough(self):
        plan = build_bucket_plan(_tree(), 256 * 1024)
        # conv.b, then conv.w (351 KiB, oversized -> own bucket), fc.w
        assert len(plan.buckets) == 3
        assert plan.passthrough  # the int32 step counter
        for b in plan.buckets:
            assert b.nbytes <= max(256 * 1024, max(b.sizes) * 4)
        covered = sorted(i for b in plan.buckets for i in b.leaf_ids)
        assert len(covered) + len(plan.passthrough) == plan.n_leaves

    def test_wire_bytes_halve_when_active(self):
        plan = build_bucket_plan(_tree(), DEFAULT_BUCKET_BYTES)
        on = gc.plan_wire_bytes(plan, GradCommConfig(compress="bf16"))
        off = gc.plan_wire_bytes(plan, GradCommConfig(compress="off"))
        assert off == plan.total_bytes and on == plan.total_bytes // 2


# ------------------------------------------------------------- round trips
class TestRoundTrip:
    def test_bf16_round_trip_bit_exact(self):
        x = jnp.asarray(np.random.RandomState(0).randn(4096), jnp.float32)
        got = gc.decompress_bucket(gc.compress_bucket(x, "bf16"))
        want = x.astype(jnp.bfloat16).astype(jnp.float32)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fp16_round_trip_bit_exact_with_clamp(self):
        x = jnp.asarray([1e30, -1e30, 3.14159, -2.5e-8], jnp.float32)
        got = gc.decompress_bucket(gc.compress_bucket(x, "fp16"))
        want = jnp.clip(x, -gc._F16_MAX, gc._F16_MAX) \
            .astype(jnp.float16).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert np.isfinite(np.asarray(got)).all()


# ------------------------------------------------------- apply_grad_comm
class TestApply:
    def test_off_returns_same_object(self):
        grads = _tree()
        out, info = apply_grad_comm(grads, None, _mesh())
        assert out is grads and info is None
        out, info = apply_grad_comm(grads, GradCommConfig(compress="off"),
                                    _mesh())
        assert out is grads and info is None

    def test_single_device_mesh_is_identity(self):
        grads = _tree()
        out, info = apply_grad_comm(grads, GradCommConfig(compress="bf16"),
                                    _mesh(1))
        assert out is grads and info is None

    def test_compress_matches_manual_cast_and_int_untouched(self):
        grads = _tree()
        mesh = _mesh()
        out, info = apply_grad_comm(grads, GradCommConfig(compress="bf16"),
                                    mesh)
        flat, _ = jax.tree_util.tree_flatten(grads)
        oflat, _ = jax.tree_util.tree_flatten(out)
        for a, b in zip(flat, oflat):
            if jnp.issubdtype(a.dtype, jnp.inexact):
                want = a.astype(jnp.bfloat16).astype(jnp.float32) \
                    .astype(a.dtype)
                np.testing.assert_array_equal(np.asarray(b),
                                              np.asarray(want))
            else:
                np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        assert info["compress"] == "bf16" and info["n_devices"] == 8
        assert info["wire_bytes"] == info["wire_bytes_f32"] // 2

    def test_error_comp_restores_bit_exact(self):
        # stateless per-step EC: dbuf + (buf - dbuf) == buf on every
        # lane (Sterbenz) — optimizer math sees the f32 gradient
        grads = _tree()
        out, info = apply_grad_comm(
            grads, GradCommConfig(compress="bf16+ec"), _mesh())
        flat, _ = jax.tree_util.tree_flatten(grads)
        oflat, _ = jax.tree_util.tree_flatten(out)
        for a, b in zip(flat, oflat):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        assert info["compress"] == "bf16+ec"


# ------------------------------------------------------------ shard_map psum
class TestCompressedPsum:
    def test_values_and_shape(self):
        mesh = _mesh()
        n = len(jax.devices())
        rs = np.random.RandomState(3)
        stacked = jnp.asarray(rs.randn(n, 257), jnp.float32)
        out = compressed_psum(stacked, mesh, "data", "bf16")
        want = np.asarray(stacked.astype(jnp.bfloat16)
                          .astype(jnp.float32)).sum(axis=0)
        assert out.shape == (257,)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-2,
                                   atol=2e-2)


# ----------------------------------------------------- 50-step convergence
class TestConvergence:
    def _train(self, compress, steps=50):
        mesh = _mesh()
        cfg = make_config(compress, "auto")
        rs = np.random.RandomState(0)
        params = {"w1": jnp.asarray(rs.randn(8, 16) * 0.3, jnp.float32),
                  "b1": jnp.zeros((16,), jnp.float32),
                  "w2": jnp.asarray(rs.randn(16, 1) * 0.3, jnp.float32)}
        x = jnp.asarray(rs.randn(64, 8), jnp.float32)
        y = jnp.asarray(np.sin(np.asarray(x).sum(axis=1, keepdims=True)),
                        jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("data")))
        y = jax.device_put(y, NamedSharding(mesh, P("data")))

        def step(params, x, y):
            def loss_fn(p):
                h = jnp.tanh(x @ p["w1"] + p["b1"])
                return jnp.mean((h @ p["w2"] - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads, _ = apply_grad_comm(grads, cfg, mesh)
            params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                            params, grads)
            return params, loss

        step = jax.jit(step)
        loss = None
        for _ in range(steps):
            params, loss = step(params, x, y)
            # sync every step: deep async pipelines of tiny sharded
            # dispatches can deadlock the virtual-device CPU runtime's
            # collective rendezvous (observed flaky hang at 8 devices)
            loss.block_until_ready()
        return float(loss)

    def test_ec_matches_f32_over_50_steps(self):
        f32 = self._train("off")
        ec = self._train("bf16+ec")
        assert ec == pytest.approx(f32, rel=1e-5, abs=1e-6)

    def test_plain_bf16_converges_within_tolerance(self):
        f32 = self._train("off")
        bf16 = self._train("bf16")
        assert bf16 == pytest.approx(f32, rel=0.05, abs=1e-3)
        assert bf16 < 0.5  # actually learned, not just close-to-broken


# ------------------------------------------------- perf harness dp parity
class TestPerfParity:
    def test_dp_parity_and_json_stamping(self):
        from bigdl_tpu.cli.perf import run

        plain = run("lenet5", 16, 4, "constant", use_bf16=False,
                    strategy="dp")
        off = run("lenet5", 16, 4, "constant", use_bf16=False,
                  strategy="dp", grad_compress="off")
        bf16 = run("lenet5", 16, 4, "constant", use_bf16=False,
                   strategy="dp", grad_compress="bf16")

        # --gradCompress off is BIT-identical to the pre-grad-comm step
        assert off["final_loss"] == plain["final_loss"]
        # compressed training tracks uncompressed within the documented
        # tolerance (PERF.md §17)
        assert bf16["final_loss"] == pytest.approx(off["final_loss"],
                                                   rel=1e-2)

        # schema-stable columns in EVERY line, active or not
        for out in (plain, off, bf16):
            assert "grad_compress" in out and "grad_buckets" in out
            json.dumps(out)  # stays JSON-serializable
        assert plain["grad_compress"] == "off"
        assert plain["grad_buckets"] is None
        assert bf16["grad_compress"] == "bf16"
        assert bf16["grad_buckets"] >= 1
        info = bf16["grad_comm"]
        assert info["wire_bytes"] * 2 == info["wire_bytes_f32"]
        assert info["n_devices"] == 8
        assert "grad_comm" not in plain

    def test_compress_without_strategy_refused(self):
        from bigdl_tpu.cli.perf import run

        with pytest.raises(SystemExit, match="multi-device"):
            run("lenet5", 16, 2, "constant", use_bf16=False,
                grad_compress="bf16")

    def test_compress_on_ep_refused(self):
        from bigdl_tpu.cli.perf import run

        with pytest.raises(SystemExit, match="reduce_grads"):
            run("lenet5", 16, 2, "constant", use_bf16=False,
                strategy="ep", grad_compress="bf16")


# --------------------------------------------------------- autotune cache
class TestAutotuneCache:
    @pytest.fixture(autouse=True)
    def _isolated(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", str(tmp_path))
        tuning.reset()
        yield tmp_path
        tuning.reset()

    def test_off_mode_returns_none(self):
        assert tuning.grad_bucket_bytes(32 * 2 ** 20, 8,
                                        "bfloat16") is None

    def test_dry_record_and_cached_replay(self, tmp_path):
        tuning.set_mode("measure")  # dry_run() on CPU -> dry placeholder
        got = tuning.grad_bucket_bytes(32 * 2 ** 20, 8, "bfloat16")
        assert got == DEFAULT_BUCKET_BYTES
        raw = open(tuning.cache_path()).read()
        assert "grad_comm|" in raw  # its own cache namespace

        tuning.reset()
        tuning.set_mode("cached")
        assert tuning.grad_bucket_bytes(32 * 2 ** 20, 8,
                                        "bfloat16") == DEFAULT_BUCKET_BYTES

    def test_cached_mode_reads_persisted_decision(self):
        from bigdl_tpu.tuning.autotune import make_key
        key = make_key("grad_comm", param_mib=32, n_devices=8,
                       dtype="bfloat16")
        c = AutotuneCache()
        c.put(key, {"config": {"bucket_bytes": 2 * 2 ** 20},
                    "source": "measured", "best_ms": 0.5})
        c.save()
        tuning.reset()
        tuning.set_mode("cached")
        assert tuning.grad_bucket_bytes(32 * 2 ** 20, 8,
                                        "bfloat16") == 2 * 2 ** 20

    def test_small_tree_clamps_candidates(self):
        # a 1.5 MiB tree must not get the 4 MiB default verbatim
        tuning.set_mode("measure")
        got = tuning.grad_bucket_bytes(int(1.5 * 2 ** 20), 8, "bfloat16")
        assert got == 2 ** 20  # largest legal candidate <= param bytes

    def test_apply_uses_tuned_bound(self):
        from bigdl_tpu.tuning.autotune import make_key
        grads = _tree()
        param_bytes = build_bucket_plan(grads,
                                        DEFAULT_BUCKET_BYTES).total_bytes
        param_mib = max(1, -(-param_bytes // 2 ** 20))
        key = make_key("grad_comm", param_mib=param_mib, n_devices=8,
                       dtype="bfloat16")
        c = AutotuneCache()
        c.put(key, {"config": {"bucket_bytes": 128 * 1024},
                    "source": "measured", "best_ms": 0.5})
        c.save()
        tuning.reset()
        tuning.set_mode("cached")
        _, info = apply_grad_comm(grads, GradCommConfig(compress="bf16"),
                                  _mesh())
        assert info["bucket_bytes"] == 128 * 1024
        assert info["bucket_source"] == "autotune"


# ---------------------------------------------------------- comm lint rules
class TestCommRules:
    def _params(self, big=True, n_small=20):
        p = {}
        if big:
            p["big"] = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
        for i in range(n_small):
            p[f"s{i}"] = jax.ShapeDtypeStruct((64,), jnp.float32)
        return p

    def test_f32_allreduce_and_unbucketed_fire(self):
        from bigdl_tpu.analysis import run_comm_rules
        r = run_comm_rules(self._params(), "dp", "off")
        rules = [f.rule for f in r.findings]
        assert "comm-f32-allreduce" in rules
        assert "comm-unbucketed" in rules

    def test_compression_silences_both(self):
        from bigdl_tpu.analysis import run_comm_rules
        assert not run_comm_rules(self._params(), "dp", "bf16").findings

    def test_single_device_strategies_exempt(self):
        from bigdl_tpu.analysis import run_comm_rules
        assert not run_comm_rules(self._params(), None, "off").findings
        assert not run_comm_rules(self._params(), "pp", "off").findings

    def test_small_model_clean(self):
        from bigdl_tpu.analysis import run_comm_rules
        r = run_comm_rules(self._params(big=False, n_small=5), "dp", "off")
        assert not r.findings


# ------------------------------------------------------------- CLI surface
class TestCli:
    def _args(self, **kw):
        ns = argparse.Namespace(strategy=None, dataParallel=False,
                                stepsPerDispatch=1, gradCompress="off",
                                gradBuckets="auto")
        for k, v in kw.items():
            setattr(ns, k, v)
        return ns

    def test_build_strategy_threads_grad_comm(self):
        from bigdl_tpu.cli.common import build_strategy
        strat = build_strategy(self._args(strategy="dp",
                                          gradCompress="bf16+ec",
                                          gradBuckets="2"))
        assert strat.grad_comm is not None
        assert strat.grad_comm.compress == "bf16+ec"
        assert strat.grad_comm.bucket_bytes == 2 * 2 ** 20

    def test_build_strategy_off_is_none(self):
        from bigdl_tpu.cli.common import build_strategy
        strat = build_strategy(self._args(strategy="dp"))
        assert strat.grad_comm is None

    def test_bad_buckets_exit(self):
        from bigdl_tpu.cli.common import make_grad_comm
        with pytest.raises(SystemExit):
            make_grad_comm(self._args(gradCompress="bf16",
                                      gradBuckets="zero"))

    def test_train_cli_exposes_flags(self):
        from bigdl_tpu.cli.common import add_train_args
        p = argparse.ArgumentParser()
        add_train_args(p)
        args = p.parse_args(["--gradCompress", "fp16+ec",
                             "--gradBuckets", "4"])
        assert args.gradCompress == "fp16+ec" and args.gradBuckets == "4"
