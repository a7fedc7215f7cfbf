"""Conv/pool layers vs torch oracle (reference torch/SpatialConvolutionSpec
etc.). Ours are NHWC; torch is NCHW — tests transpose at the boundary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from bigdl_tpu import nn
from bigdl_tpu.utils import check_gradients

R = np.random.RandomState(11)


def nhwc(x_nchw):
    return np.ascontiguousarray(np.transpose(x_nchw, (0, 2, 3, 1)))


def nchw(x_nhwc):
    return np.ascontiguousarray(np.transpose(x_nhwc, (0, 3, 1, 2)))


def torch_weight(p):  # HWIO -> OIHW
    return torch.from_numpy(np.ascontiguousarray(
        np.transpose(np.asarray(p["weight"]), (3, 2, 0, 1))))


@pytest.mark.parametrize("stride,pad,groups", [
    (1, 0, 1), (2, 1, 1), (1, 2, 1), (1, 0, 2), (2, 1, 4),
])
def test_spatial_convolution_vs_torch(rng, stride, pad, groups):
    cin, cout, k = 4, 8, 3
    mod = nn.SpatialConvolution(cin, cout, k, k, stride, stride, pad, pad,
                                n_group=groups)
    p = mod.init(rng)
    x = R.randn(2, cin, 9, 9).astype(np.float32)
    ours = nchw(np.asarray(mod.forward(p, jnp.asarray(nhwc(x)))))
    theirs = F.conv2d(torch.from_numpy(x), torch_weight(p),
                      torch.from_numpy(np.asarray(p["bias"])),
                      stride=stride, padding=pad, groups=groups).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


def test_dilated_convolution_vs_torch(rng):
    mod = nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2,
                                       dilation_w=2, dilation_h=2)
    p = mod.init(rng)
    x = R.randn(2, 3, 10, 10).astype(np.float32)
    ours = nchw(np.asarray(mod.forward(p, jnp.asarray(nhwc(x)))))
    theirs = F.conv2d(torch.from_numpy(x), torch_weight(p),
                      torch.from_numpy(np.asarray(p["bias"])),
                      padding=2, dilation=2).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


@pytest.mark.parametrize("stride,pad,adj", [(2, 1, 0), (2, 1, 1), (1, 0, 0)])
def test_full_convolution_vs_torch(rng, stride, pad, adj):
    cin, cout, k = 3, 5, 3
    mod = nn.SpatialFullConvolution(cin, cout, k, k, stride, stride,
                                    pad, pad, adj, adj)
    p = mod.init(rng)
    x = R.randn(2, cin, 6, 6).astype(np.float32)
    ours = nchw(np.asarray(mod.forward(p, jnp.asarray(nhwc(x)))))
    # our HWIO weight (kh,kw,cin,cout) -> torch transposed-conv IOHW
    # with spatially *unflipped* kernel: conv_transpose2d's kernel is applied
    # flipped relative to the gradient formulation, matching our flip.
    w = torch.from_numpy(np.ascontiguousarray(
        np.transpose(np.asarray(p["weight"]), (2, 3, 0, 1))))
    theirs = F.conv_transpose2d(
        torch.from_numpy(x), w, torch.from_numpy(np.asarray(p["bias"])),
        stride=stride, padding=pad, output_padding=adj).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


def test_convolution_map_depthwise(rng):
    table = nn.SpatialConvolutionMap.one_to_one(3)
    mod = nn.SpatialConvolutionMap(table, 3, 3, pad_w=1, pad_h=1)
    p = mod.init(rng)
    x = R.randn(2, 3, 6, 6).astype(np.float32)
    ours = nchw(np.asarray(mod.forward(p, jnp.asarray(nhwc(x)))))
    # depthwise equivalent in torch: groups=3 conv with masked weights
    w_full = np.transpose(np.asarray(p["weight"]), (3, 2, 0, 1))  # OIHW
    w_dw = np.stack([w_full[i, i] for i in range(3)])[:, None]  # (3,1,3,3)
    theirs = F.conv2d(torch.from_numpy(x), torch.from_numpy(w_dw),
                      torch.from_numpy(np.asarray(p["bias"])),
                      padding=1, groups=3).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


def test_temporal_convolution(rng):
    mod = nn.TemporalConvolution(6, 4, 3, pad_w=1)
    p = mod.init(rng)
    x = R.randn(2, 10, 6).astype(np.float32)
    ours = np.asarray(mod.forward(p, jnp.asarray(x)))
    w = torch.from_numpy(np.ascontiguousarray(
        np.transpose(np.asarray(p["weight"]), (2, 1, 0))))  # (out,in,k)
    theirs = F.conv1d(torch.from_numpy(x.transpose(0, 2, 1)), w,
                      torch.from_numpy(np.asarray(p["bias"])),
                      padding=1).numpy().transpose(0, 2, 1)
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


@pytest.mark.parametrize("k,s,pad,ceil", [
    (2, 2, 0, False), (3, 2, 1, False), (3, 2, 1, True), (3, 1, 0, False),
])
def test_max_pooling_vs_torch(k, s, pad, ceil):
    x = R.randn(2, 3, 7, 7).astype(np.float32)
    mod = nn.SpatialMaxPooling(k, k, s, s, pad, pad, ceil_mode=ceil)
    ours = nchw(np.asarray(mod.forward({}, jnp.asarray(nhwc(x)))))
    theirs = F.max_pool2d(torch.from_numpy(x), k, s, pad,
                          ceil_mode=ceil).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-6)


@pytest.mark.parametrize("k,s,pad,ceil", [
    (2, 2, 0, False), (3, 2, 1, False), (3, 2, 1, True),
])
def test_avg_pooling_vs_torch(k, s, pad, ceil):
    x = R.randn(2, 3, 7, 7).astype(np.float32)
    mod = nn.SpatialAveragePooling(k, k, s, s, pad, pad, ceil_mode=ceil)
    ours = nchw(np.asarray(mod.forward({}, jnp.asarray(nhwc(x)))))
    theirs = F.avg_pool2d(torch.from_numpy(x), k, s, pad,
                          ceil_mode=ceil).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-6)


def test_conv_gradcheck(rng):
    mod = nn.SpatialConvolution(2, 3, 3, 3, pad_w=1, pad_h=1)
    p = mod.init(rng)
    x = jnp.asarray(R.randn(2, 5, 5, 2).astype(np.float32))

    def loss(params):
        return jnp.sum(jnp.square(mod.forward(params, x)))

    check_gradients(loss, p)


def test_full_conv_bilinear_filler_upsamples():
    """init="bilinear" (reference BilinearFiller,
    SpatialFullConvolution.scala:121): a stride-2 4x4 deconv initialized
    bilinear reproduces torch's bilinear-upsample on the interior, and a
    constant image maps to the same constant."""
    import torch.nn.functional as F

    from bigdl_tpu.nn import SpatialFullConvolution

    m = SpatialFullConvolution(1, 1, 4, 4, 2, 2, 1, 1, with_bias=False,
                               init="bilinear_upsample")
    p = m.init(jax.random.PRNGKey(0))

    ones = jnp.ones((1, 5, 5, 1), jnp.float32)
    out = np.asarray(m.forward(p, ones))[0, :, :, 0]
    np.testing.assert_allclose(out[1:-1, 1:-1], 1.0, atol=1e-6)

    rs = np.random.RandomState(0)
    x = rs.randn(1, 6, 6, 1).astype(np.float32)
    got = np.asarray(m.forward(p, jnp.asarray(x)))[0, :, :, 0]
    want = F.interpolate(torch.from_numpy(x.transpose(0, 3, 1, 2)),
                         scale_factor=2, mode="bilinear",
                         align_corners=False).numpy()[0, 0]
    # interiors agree exactly; borders differ by the padding convention
    np.testing.assert_allclose(got[2:-2, 2:-2], want[2:-2, 2:-2],
                               atol=1e-5)


def test_bilinear_filler_reference_vs_upsample_variants():
    """init="bilinear" matches the reference BilinearFiller exactly
    (SpatialFullConvolution.scala:121-135: EVERY channel pair filled with
    the triangle kernel); init="bilinear_upsample" is the diagonal FCN
    variant (cross-channel taps zero). They agree at 1->1 channels."""
    from bigdl_tpu.nn import SpatialFullConvolution

    ref = SpatialFullConvolution(3, 2, 4, 4, 2, 2, 1, 1, init="bilinear")
    w = np.asarray(ref.init(jax.random.PRNGKey(0))["weight"])
    # reference formula, computed independently per element
    f = int(np.ceil(4 / 2.0))
    c = (2 * f - 1 - f % 2) / (2.0 * f)
    tri = np.array([[(1 - abs(x / f - c)) * (1 - abs(y / f - c))
                     for x in range(4)] for y in range(4)], np.float32)
    for i in range(3):
        for o in range(2):
            np.testing.assert_allclose(w[:, :, i, o], tri, atol=1e-6)

    up = SpatialFullConvolution(3, 2, 4, 4, 2, 2, 1, 1,
                                init="bilinear_upsample")
    wu = np.asarray(up.init(jax.random.PRNGKey(0))["weight"])
    np.testing.assert_allclose(wu[:, :, 0, 0], tri, atol=1e-6)
    assert np.all(wu[:, :, 0, 1] == 0)  # cross-channel taps zeroed


class TestConvLayoutPolicy:
    """Per-pass conv layout policy (ops/conv2d.py, VERDICT r4 weak #4):
    any fwd/dgrad/wgrad layout combination must be numerically identical
    to the default NHWC path — the policy only steers XLA's layout
    assignment, never the math."""

    def teardown_method(self):
        from bigdl_tpu.ops.conv2d import reset_conv_pass_layouts
        reset_conv_pass_layouts()  # default + clear the explicit flag

    def _loss_and_grads(self, mod, params, x):
        def loss(p, xx):
            y, _ = mod.apply(p, {}, xx, training=True)
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        l, g = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        return np.asarray(l), jax.tree_util.tree_map(np.asarray, g)

    @pytest.mark.parametrize("layouts", [
        ("NCHW", "NCHW", "NCHW"),
        ("NHWC", "NCHW", "NHWC"),
        ("NHWC", "NHWC", "NCHW"),
        ("NCHW", "NHWC", "NHWC"),
    ])
    def test_policy_matches_default_path(self, layouts, rng):
        from bigdl_tpu import nn
        from bigdl_tpu.ops import set_conv_pass_layouts

        mod = nn.SpatialConvolution(3, 8, 3, 3, stride_w=2, stride_h=2,
                                    pad_w=1, pad_h=1)
        params = mod.init(rng)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 8, 3),
                        jnp.float32)
        l0, (gp0, gx0) = self._loss_and_grads(mod, params, x)
        set_conv_pass_layouts(*layouts)
        l1, (gp1, gx1) = self._loss_and_grads(mod, params, x)
        np.testing.assert_allclose(l1, l0, rtol=1e-5)
        np.testing.assert_allclose(gx1, gx0, atol=1e-4)
        np.testing.assert_allclose(gp1["weight"], gp0["weight"], atol=1e-4)
        np.testing.assert_allclose(gp1["bias"], gp0["bias"], atol=1e-4)

    def test_grouped_and_dilated_under_policy(self, rng):
        from bigdl_tpu import nn
        from bigdl_tpu.ops import set_conv_pass_layouts

        g = nn.SpatialConvolution(4, 8, 3, 3, pad_w=1, pad_h=1, n_group=2)
        d = nn.SpatialDilatedConvolution(3, 6, 3, 3, pad_w=2, pad_h=2,
                                         dilation_w=2, dilation_h=2)
        gp, dp = g.init(rng), d.init(jax.random.PRNGKey(9))
        xg = jnp.asarray(np.random.RandomState(1).randn(2, 6, 6, 4),
                         jnp.float32)
        xd = jnp.asarray(np.random.RandomState(2).randn(2, 7, 7, 3),
                         jnp.float32)
        lg0, (ggp0, ggx0) = self._loss_and_grads(g, gp, xg)
        ld0, (dgp0, dgx0) = self._loss_and_grads(d, dp, xd)
        set_conv_pass_layouts("NCHW", "NCHW", "NCHW")
        lg1, (ggp1, ggx1) = self._loss_and_grads(g, gp, xg)
        ld1, (dgp1, dgx1) = self._loss_and_grads(d, dp, xd)
        np.testing.assert_allclose(lg1, lg0, rtol=1e-5)
        np.testing.assert_allclose(ld1, ld0, rtol=1e-5)
        np.testing.assert_allclose(ggx1, ggx0, atol=1e-4)
        np.testing.assert_allclose(dgx1, dgx0, atol=1e-4)
        np.testing.assert_allclose(ggp1["weight"], ggp0["weight"], atol=1e-4)
        np.testing.assert_allclose(dgp1["weight"], dgp0["weight"], atol=1e-4)

    def test_decide_from_probe(self):
        from bigdl_tpu.ops import decide_from_probe

        rows = [
            {"layout": "NHWC", "fwd_ms": 1.0, "dgrad_ms": 5.0,
             "wgrad_ms": 2.0},
            {"layout": "NCHW", "fwd_ms": 2.0, "dgrad_ms": 3.0,
             "wgrad_ms": 2.5},
            {"layout": "NHWC", "fwd_ms": 1.0, "dgrad_ms": 5.0,
             "wgrad_ms": 2.0},
            {"layout": "NCHW", "fwd_ms": 2.0, "dgrad_ms": 3.0,
             "wgrad_ms": 2.5},
        ]
        import json as _json
        d = decide_from_probe([_json.dumps(r) for r in rows])
        assert d == {"fwd": "NHWC", "dgrad": "NCHW", "wgrad": "NHWC"}
        with pytest.raises(ValueError, match="no probe rows"):
            decide_from_probe(["not json", ""])


class TestShippedLayoutDecision:
    """The measured probe decision ships as the framework default
    (ops/conv2d.MEASURED_DECISIONS, window-2 provenance in PERF.md §8.2):
    'auto' resolves per device kind, explicit installs win over auto."""

    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    def teardown_method(self):
        from bigdl_tpu.ops.conv2d import reset_conv_pass_layouts
        reset_conv_pass_layouts()

    def test_resolve_spec(self):
        from bigdl_tpu.ops.conv2d import resolve_layout_spec

        assert resolve_layout_spec("default") == {
            "fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NHWC"}
        assert resolve_layout_spec("nhwc,nchw,nchw") == {
            "fwd": "NHWC", "dgrad": "NCHW", "wgrad": "NCHW"}
        # the measured v5e decision: wgrad-NCHW
        assert resolve_layout_spec(
            "auto", self._Dev("TPU v5 lite")) == {
            "fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NCHW"}
        # unmeasured device -> safe no-op default
        assert resolve_layout_spec(
            "auto", self._Dev("TPU v9 colossal")) == {
            "fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NHWC"}
        with pytest.raises(ValueError, match="convLayout spec"):
            resolve_layout_spec("NHWC,NCHW")

    def test_auto_install_and_explicit_precedence(self):
        from bigdl_tpu.ops.conv2d import (get_conv_pass_layouts,
                                          maybe_install_auto,
                                          reset_conv_pass_layouts,
                                          set_conv_pass_layouts)

        reset_conv_pass_layouts()
        # auto install resolves the measured decision for the device
        pol = maybe_install_auto(self._Dev("TPU v5 lite"))
        assert pol["wgrad"] == "NCHW"
        assert get_conv_pass_layouts() == pol
        # an explicit install (CLI --convLayout / API) wins over a later
        # auto attempt — the Optimizer must not stomp user choices
        set_conv_pass_layouts("NCHW", "NCHW", "NCHW")
        pol = maybe_install_auto(self._Dev("TPU v5 lite"))
        assert pol == {"fwd": "NCHW", "dgrad": "NCHW", "wgrad": "NCHW"}
        # ...including an explicit request for the all-NHWC default
        reset_conv_pass_layouts()
        set_conv_pass_layouts()
        pol = maybe_install_auto(self._Dev("TPU v5 lite"))
        assert pol == {"fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NHWC"}

    def test_install_layout_spec_auto_on_cpu_is_noop(self):
        # 'auto' on an unmeasured device resolves to default: training
        # paths unchanged (fake device, not the ambient backend — this
        # suite also runs unfiltered on the TPU capture host)
        from bigdl_tpu.ops.conv2d import (install_layout_spec,
                                          is_default_policy)

        install_layout_spec("auto", self._Dev("cpu"))
        assert is_default_policy()


def test_decide_from_probe_rejects_truncated_coverage():
    """A probe killed mid-run leaves one layout with fewer rows
    (or none) — deciding from that would let an unmeasured layout win at
    0.0 ms (review r5)."""
    import json as _json

    from bigdl_tpu.ops import decide_from_probe

    only_nhwc = [_json.dumps({"layout": "NHWC", "fwd_ms": 1.0,
                              "dgrad_ms": 1.0, "wgrad_ms": 1.0})]
    with pytest.raises(ValueError, match="asymmetric probe coverage"):
        decide_from_probe(only_nhwc)
