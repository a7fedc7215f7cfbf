"""ISSUE 3: per-conv-geometry layout policy + 1x1-conv-as-GEMM.

Covers the acceptance list:
* numerical parity vs the global-triple (all-NHWC) path for every
  (layout x pass) combination including the GEMM path — f32 gradcheck
  and bf16 tolerance;
* geometry-key round-trip through the autotune cache (dry measure →
  cached replay), probe decisions persisted via put_geom_decisions;
* snapshot/restore with mixed per-geometry + global state;
* probe-JSONL → decisions → installed policy deterministic round-trip
  (satellite #6);
* bench hygiene satellites (vs_baseline null, pipe row dropped,
  hard-grade TTA pinned);
* a ``-m tpu`` compiled smoke at the bottom.
"""

import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import tuning
from bigdl_tpu.ops import conv2d as c2d


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Private autotune cache + pristine tuner and conv policy (all
    process-global trace-time state)."""
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", str(tmp_path))
    tuning.reset()
    c2d.reset_conv_pass_layouts()
    yield tmp_path
    tuning.reset()
    c2d.reset_conv_pass_layouts()


def _geom_json(kh, kw, stride, cin, cout, dtype="float32", groups=1,
               dilation=(1, 1)):
    return {"kh": kh, "kw": kw, "stride": [stride, stride], "cin": cin,
            "cout": cout, "groups": groups,
            "dilation": list(dilation), "dtype": dtype}


def _run(x, w, stride=(1, 1), padding=((0, 0), (0, 0))):
    """(y, dx, dw) through the policy-routed custom vjp."""
    args = (stride, padding, (1, 1), 1)

    def loss(x_, w_):
        return jnp.sum(c2d.conv2d(x_, w_, *args) ** 2)

    y = c2d.conv2d(x, w, *args)
    dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
    return (np.asarray(y, np.float32), np.asarray(dx, np.float32),
            np.asarray(dw, np.float32))


# ------------------------------------------------------------ parity
class TestLayoutPassParity:
    """Every (pass x layout) combination matches the all-NHWC reference
    on the same inputs — the per-geometry policy may only change HOW a
    pass compiles, never what it computes."""

    @pytest.mark.parametrize("pass_name,layout", list(itertools.product(
        ("fwd", "dgrad", "wgrad"), ("NHWC", "NCHW", "GEMM"))))
    def test_one_pass_one_layout_f32(self, pass_name, layout):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(2, 6, 6, 8), jnp.float32)
        w = jnp.asarray(rs.randn(1, 1, 8, 16), jnp.float32)
        ref = _run(x, w)
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 8, 16),
            "layouts": {pass_name: layout}}])
        got = _run(x, w)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_all_passes_mixed_layouts_bf16(self):
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(2, 6, 6, 8), jnp.bfloat16)
        w = jnp.asarray(rs.randn(1, 1, 8, 16), jnp.bfloat16)
        ref = _run(x, w)
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 8, 16, "bfloat16"),
            "layouts": {"fwd": "GEMM", "dgrad": "NCHW",
                        "wgrad": "GEMM"}}])
        got = _run(x, w)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)

    def test_gemm_gradcheck_f32(self):
        """Finite differences against the custom-vjp GEMM backward —
        catches a wrong linear_transpose the parity-vs-autodiff check
        could share."""
        from bigdl_tpu.utils.gradcheck import check_gradients

        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(1, 4, 4, 4), jnp.float32)
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 4, 6),
            "layouts": {"fwd": "GEMM", "dgrad": "GEMM",
                        "wgrad": "GEMM"}}])

        def loss(p):
            y = c2d.conv2d(x, p["w"], (1, 1), ((0, 0), (0, 0)),
                           (1, 1), 1)
            return jnp.sum(y ** 2)

        check_gradients(loss, {"w": jnp.asarray(
            rs.randn(1, 1, 4, 6), jnp.float32)})

    def test_gemm_actually_emits_dot_general(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(2, 4, 4, 8), jnp.float32)
        w = jnp.asarray(rs.randn(1, 1, 8, 8), jnp.float32)
        args = ((1, 1), ((0, 0), (0, 0)), (1, 1), 1)
        plain = str(jax.make_jaxpr(
            lambda a, b: c2d.conv2d(a, b, *args))(x, w))
        assert "dot_general" not in plain
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 8, 8),
            "layouts": {"fwd": "GEMM"}}])
        gemm = str(jax.make_jaxpr(
            lambda a, b: c2d.conv2d(a, b, *args))(x, w))
        assert "dot_general" in gemm

    def test_gemm_ineligible_site_falls_back_exactly(self):
        """A GEMM decision at a 3x3 (or strided/padded) site degrades to
        NHWC — same numbers as the default path, never an error."""
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(2, 8, 8, 4), jnp.float32)
        w = jnp.asarray(rs.randn(3, 3, 4, 4), jnp.float32)
        ref = _run(x, w, (2, 2), ((1, 1), (1, 1)))
        c2d.install_geom_decisions([{
            "geom": _geom_json(3, 3, 2, 4, 4),
            "layouts": {"fwd": "GEMM", "dgrad": "GEMM",
                        "wgrad": "GEMM"}}])
        got = _run(x, w, (2, 2), ((1, 1), (1, 1)))
        for a, b in zip(ref, got):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_explicit_conv_layout_wins_over_geometry(self):
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(1, 4, 4, 4), jnp.float32)
        w = jnp.asarray(rs.randn(1, 1, 4, 4), jnp.float32)
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 4, 4),
            "layouts": {"fwd": "GEMM"}}])
        c2d.set_conv_pass_layouts("NHWC", "NHWC", "NHWC")  # explicit
        args = ((1, 1), ((0, 0), (0, 0)), (1, 1), 1)
        jx = str(jax.make_jaxpr(
            lambda a, b: c2d.conv2d(a, b, *args))(x, w))
        assert "dot_general" not in jx  # geometry decision suppressed

    def test_gemm_in_explicit_spec(self):
        pol = c2d.resolve_layout_spec("NHWC,NHWC,GEMM")
        assert pol == {"fwd": "NHWC", "dgrad": "NHWC", "wgrad": "GEMM"}
        with pytest.raises(ValueError):
            c2d.resolve_layout_spec("NHWC,GEM,NHWC")

    def test_module_level_parity_through_policy(self):
        """nn.SpatialConvolution routes through the custom vjp whenever a
        policy can apply and matches its plain path bit-for-bit under
        all-NHWC decisions."""
        from bigdl_tpu import nn

        m = nn.SpatialConvolution(8, 16, 1, 1)
        params = m.init(jax.random.PRNGKey(0))
        rs = np.random.RandomState(6)
        x = jnp.asarray(rs.randn(2, 5, 5, 8), jnp.float32)
        y_ref, _ = m.apply(params, {}, x, training=True, rng=None)
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 8, 16),
            "layouts": {"fwd": "GEMM", "dgrad": "GEMM",
                        "wgrad": "NCHW"}}])
        assert c2d.policy_active()
        y_pol, _ = m.apply(params, {}, x, training=True, rng=None)
        np.testing.assert_allclose(np.asarray(y_pol), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------- autotune cache round-trip
class TestGeomCacheRoundTrip:
    def test_dry_measure_populates_conv_geom_keys(self, tmp_path):
        tuning.set_mode("measure")
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(1, 4, 4, 8), jnp.float32)
        w = jnp.asarray(rs.randn(1, 1, 8, 8), jnp.float32)
        _run(x, w)
        ents = tuning.get_cache().entries
        geom_keys = [k for k in ents if k.startswith("conv_geom|")]
        assert len(geom_keys) == 3  # fwd + dgrad + wgrad of one geometry
        for k in geom_keys:
            assert ents[k] == {"config": {"layout": "NHWC"},
                               "source": "dry"}
        key = tuning.conv_geom_key(
            "wgrad", (1, 1, 1, 1, 8, 8, 1, 1, 1, "float32"))
        assert key in ents

    def test_cached_probe_decision_applies_and_is_recorded(self):
        geom = _geom_json(1, 1, 1, 8, 8)
        tuning.put_geom_decisions([
            {"geom": geom, "layouts": {"fwd": "GEMM", "wgrad": "NCHW"}}])
        tuning.reset()
        tuning.set_mode("cached")
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(1, 4, 4, 8), jnp.float32)
        w = jnp.asarray(rs.randn(1, 1, 8, 8), jnp.float32)
        args = ((1, 1), ((0, 0), (0, 0)), (1, 1), 1)
        jx = str(jax.make_jaxpr(
            lambda a, b: c2d.conv2d(a, b, *args))(x, w))
        assert "dot_general" in jx  # the cached GEMM decision compiled in
        ann = tuning.annotation()
        key = tuning.conv_geom_key(
            "fwd", (1, 1, 1, 1, 8, 8, 1, 1, 1, "float32"))
        assert ann["decisions"][key] == {"layout": "GEMM",
                                         "source": "cached"}

    def test_gemm_cache_entry_at_ineligible_site_ignored(self, tmp_path):
        """A conv_geom GEMM entry for a 3x3 geometry (hand-edited or
        stale) must not crash the trace — cached mode falls back to the
        global triple."""
        geom = (3, 3, 1, 1, 4, 4, 1, 1, 1, "float32")
        c = tuning.get_cache()
        c.put(tuning.conv_geom_key("fwd", geom),
              {"config": {"layout": "GEMM"}, "source": "probe"})
        c.save()
        tuning.reset()
        tuning.set_mode("cached")
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(1, 6, 6, 4), jnp.float32)
        w = jnp.asarray(rs.randn(3, 3, 4, 4), jnp.float32)
        ref = _run(x, w, (1, 1), ((1, 1), (1, 1)))
        assert all(np.isfinite(a).all() for a in ref)

    def test_dry_measure_cache_is_byte_identical(self, tmp_path):
        def populate():
            tuning.reset()
            tuning.set_mode("measure")
            rs = np.random.RandomState(0)
            x = jnp.asarray(rs.randn(1, 4, 4, 8), jnp.float32)
            w1 = jnp.asarray(rs.randn(1, 1, 8, 8), jnp.float32)
            w3 = jnp.asarray(rs.randn(3, 3, 8, 8), jnp.float32)
            _run(x, w1)
            _run(x, w3, (1, 1), ((1, 1), (1, 1)))
            with open(tuning.cache_path()) as f:
                return f.read()

        first = populate()
        assert populate() == first
        os.unlink(tuning.cache_path())
        assert populate() == first


# ------------------------------------------------------ snapshot/restore
class TestMixedSnapshotRestore:
    def test_mixed_global_and_geometry_state(self):
        c2d.set_conv_pass_layouts("NHWC", "NCHW", "NCHW")
        c2d.install_geom_decisions([{
            "geom": _geom_json(7, 7, 2, 3, 64, "bfloat16"),
            "layouts": {"wgrad": "NCHW"}}])
        snap = c2d.policy_snapshot()
        c2d.reset_conv_pass_layouts()
        assert c2d.geom_policy_if_any() is None
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 64, 256, "bfloat16"),
            "layouts": {"fwd": "GEMM"}}])
        c2d.restore_policy(snap)
        assert c2d.get_conv_pass_layouts() == {
            "fwd": "NHWC", "dgrad": "NCHW", "wgrad": "NCHW"}
        gp = c2d.geom_policy_if_any()
        assert len(gp) == 1 and gp[0]["layouts"] == {"wgrad": "NCHW"}
        # the explicit flag came back too
        pol = c2d.maybe_install_auto()
        assert pol["dgrad"] == "NCHW"

    def test_legacy_two_tuple_snapshot_restores(self):
        c2d.install_geom_decisions([{
            "geom": _geom_json(1, 1, 1, 4, 4),
            "layouts": {"fwd": "GEMM"}}])
        c2d.restore_policy(({"fwd": "NHWC", "dgrad": "NHWC",
                             "wgrad": "NHWC"}, False))
        assert c2d.geom_policy_if_any() is None
        assert not c2d.policy_active()

    def test_perf_run_restores_geometry_table(self):
        """cli.perf.run snapshots/restores the WHOLE policy — a geometry
        table installed inside a run cannot leak across runs."""
        from bigdl_tpu.cli import perf

        c2d.install_geom_decisions([{
            "geom": _geom_json(5, 5, 1, 1, 6),
            "layouts": {"fwd": "NCHW"}}])
        before = c2d.policy_snapshot()
        perf.run("lenet5", 2, 1, "random", use_bf16=False)
        assert c2d.policy_snapshot() == before


# ------------------------------------------- probe → decisions (satellite)
def _synth_probe_lines():
    """Two-geometry probe with explicit fields: a 7x7/s2 stem whose wgrad
    prefers NCHW, and a 1x1/s1 conv whose wgrad prefers GEMM."""
    rows = []
    stem = _geom_json(7, 7, 2, 3, 64, "bfloat16")
    one = _geom_json(1, 1, 1, 512, 128, "bfloat16")
    rows.append({"shape": "stem", "layout": "NHWC", **stem,
                 "fwd_ms": 0.021, "dgrad_ms": 0.023, "wgrad_ms": 0.146,
                 "gflops": 30.2})
    rows.append({"shape": "stem", "layout": "NCHW", **stem,
                 "fwd_ms": 0.026, "dgrad_ms": 0.029, "wgrad_ms": 0.021,
                 "gflops": 30.2})
    rows.append({"shape": "one", "layout": "NHWC", **one,
                 "fwd_ms": 0.030, "dgrad_ms": 0.019, "wgrad_ms": 0.026,
                 "gflops": 13.2})
    rows.append({"shape": "one", "layout": "NCHW", **one,
                 "fwd_ms": 0.025, "dgrad_ms": 0.022, "wgrad_ms": 0.029,
                 "gflops": 13.2})
    rows.append({"shape": "one", "layout": "GEMM", **one,
                 "fwd_ms": 0.024, "dgrad_ms": 0.021, "wgrad_ms": 0.018,
                 "gflops": 13.2})
    return [json.dumps(r) for r in rows]


class TestProbeToPolicyRoundTrip:
    def test_decisions_deterministic_and_install_round_trips(self):
        lines = _synth_probe_lines()
        d1 = c2d.decide_geom_from_probe(lines)
        d2 = c2d.decide_geom_from_probe(list(reversed(lines)))
        assert json.dumps(d1, sort_keys=True) == json.dumps(d2,
                                                            sort_keys=True)
        stem = [d for d in d1 if d["geom"]["kh"] == 7][0]
        assert stem["layouts"] == {"fwd": "NHWC", "dgrad": "NHWC",
                                   "wgrad": "NCHW"}
        one = [d for d in d1 if d["geom"]["kh"] == 1][0]
        assert one["layouts"] == {"fwd": "GEMM", "dgrad": "NHWC",
                                  "wgrad": "GEMM"}
        assert c2d.install_geom_decisions(d1) == 2
        assert c2d.geom_policy_if_any() == d1  # installed == decided

    def test_legacy_rows_map_through_shape_names(self):
        with open("CONV_PROBE_r05.jsonl") as f:
            lines = f.read().splitlines()
        d = c2d.decide_geom_from_probe(lines)
        assert len(d) == len(c2d.LEGACY_PROBE_SHAPES)
        stem = [x for x in d if x["geom"]["kh"] == 7][0]
        assert stem["layouts"]["wgrad"] == "NCHW"  # the measured 7x case
        assert stem["layouts"]["fwd"] == "NHWC"

    def test_apply_conv_probe_geom_cli(self, tmp_path, capsys):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "apply_conv_probe", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "scripts", "apply_conv_probe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        probe = tmp_path / "probe.jsonl"
        probe.write_text("\n".join(_synth_probe_lines()) + "\n")
        mod.main(["--geom", "--cache", str(probe)])
        blob = json.loads(capsys.readouterr().out)
        assert len(blob["decisions"]) == 2
        # ...and the cache namespace replays them
        tuning.reset()
        tuning.set_mode("cached")
        geom = (1, 1, 1, 1, 512, 128, 1, 1, 1, "bfloat16")
        ent = tuning.get_cache().get(tuning.conv_geom_key("wgrad", geom))
        assert ent == {"config": {"layout": "GEMM"}, "source": "probe"}

    def test_install_rejects_bad_decision(self):
        with pytest.raises(ValueError):
            c2d.install_geom_decisions([{
                "geom": _geom_json(1, 1, 1, 4, 4),
                "layouts": {"fwd": "IM2COL"}}])
        with pytest.raises(ValueError):
            c2d.install_geom_decisions([{"geom": {"kh": 1},
                                         "layouts": {"fwd": "NHWC"}}])

    def test_install_geom_file_and_cli_flag(self, tmp_path):
        f = tmp_path / "geom.json"
        f.write_text(json.dumps({"decisions": [
            {"geom": _geom_json(1, 1, 1, 8, 8),
             "layouts": {"wgrad": "GEMM"}}]}))
        assert c2d.install_geom_file(str(f)) == 1
        c2d.reset_conv_pass_layouts()
        # the CLI spelling (apply_platform) installs the same file
        import argparse

        from bigdl_tpu.cli.common import apply_platform
        apply_platform(argparse.Namespace(platform=None, autotune=None,
                                          convLayout=None,
                                          convGeom=str(f)))
        gp = c2d.geom_policy_if_any()
        assert gp and gp[0]["layouts"] == {"wgrad": "GEMM"}
        with pytest.raises(SystemExit):
            apply_platform(argparse.Namespace(
                platform=None, autotune=None, convLayout=None,
                convGeom=str(tmp_path / "missing.json")))


# -------------------------------------------------- perf JSON provenance
def test_perf_line_stamps_geom_policy():
    from bigdl_tpu.cli import perf

    c2d.install_geom_decisions([{
        "geom": _geom_json(5, 5, 1, 1, 6),
        "layouts": {"wgrad": "NCHW"}}])
    out = perf.run("lenet5", 2, 1, "random", use_bf16=False)
    assert out["conv_geom"] == [{
        "geom": _geom_json(5, 5, 1, 1, 6),
        "layouts": {"wgrad": "NCHW"}}]


# --------------------------------------------------------- compiled (TPU)
@pytest.mark.tpu
def test_conv_geom_compiled_on_tpu():
    """Chip smoke: a per-geometry policy mixing NCHW and GEMM compiles
    and matches the default path on a small conv stack."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU backend")
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(8, 14, 14, 128), jnp.bfloat16)
    w1 = jnp.asarray(rs.randn(1, 1, 128, 256), jnp.bfloat16)
    w3 = jnp.asarray(rs.randn(3, 3, 256, 256), jnp.bfloat16)

    def loss(x_, a, b):
        y = c2d.conv2d(x_, a, (1, 1), ((0, 0), (0, 0)), (1, 1), 1)
        y = c2d.conv2d(y, b, (1, 1), ((1, 1), (1, 1)), (1, 1), 1)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(1, 2)))
    ref = jax.tree_util.tree_map(np.asarray, g(x, w1, w3))
    c2d.install_geom_decisions([
        {"geom": _geom_json(1, 1, 1, 128, 256, "bfloat16"),
         "layouts": {"fwd": "GEMM", "dgrad": "GEMM", "wgrad": "GEMM"}},
        {"geom": _geom_json(3, 3, 1, 256, 256, "bfloat16"),
         "layouts": {"wgrad": "NCHW"}}])
    got = jax.tree_util.tree_map(np.asarray,
                                 jax.jit(jax.grad(loss,
                                                  argnums=(1, 2)))(
                                     x, w1, w3))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32),
                                   rtol=5e-2, atol=5e-1)
    c2d.reset_conv_pass_layouts()
