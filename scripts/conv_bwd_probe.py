"""Conv backward layout probe (PERF.md §2: the backward runs at ~38% MFU
vs the forward's 46% — this isolates WHERE).

For each representative ResNet-50 conv shape, times the three conv passes
separately (forward, input-grad, filter-grad) in bf16, for both NHWC and
NCHW activation layouts — and, where the shape is exactly a matmul (1x1,
stride 1), for the GEMM spelling (``dot_general`` over flattened pixels,
the ops/conv2d.py round-8 layout choice). XLA picks internal layouts per
op; what the framework controls is the activation layout (or the matmul
spelling) it hands XLA — if NCHW or GEMM wins some pass for some shape
class, the per-geometry policy (ops/conv2d.py, ISSUE 3) is the lever.

Every row carries its geometry fields (kh/kw/stride/cin/cout/groups/
dilation/dtype), so ``scripts/apply_conv_probe.py --geom`` can turn the
JSONL directly into per-geometry decisions; rows from older probes
(name-only) are mapped through ops/conv2d.LEGACY_PROBE_SHAPES.

Usage: python scripts/conv_bwd_probe.py [iters]   # one JSON line per cell
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.utils.flops import conv_unit_flops  # noqa: E402

# (name, batch, h, w, cin, cout, k, stride)
SHAPES = [
    ("stem7x7s2", 128, 224, 224, 3, 64, 7, 2),
    ("s1_3x3", 128, 56, 56, 64, 64, 3, 1),
    ("s2_3x3", 128, 28, 28, 128, 128, 3, 1),
    ("s3_3x3", 128, 14, 14, 256, 256, 3, 1),
    ("s4_3x3", 128, 7, 7, 512, 512, 3, 1),
    ("s2_1x1", 128, 28, 28, 512, 128, 1, 1),
    # the two remaining 1x1 families (~half of ResNet-50's FLOPs are
    # 1x1 GEMMs): bottleneck expand and reduce at stage-1 width
    ("s1_1x1_expand", 128, 56, 56, 64, 256, 1, 1),
    ("s1_1x1_reduce", 128, 56, 56, 256, 64, 1, 1),
]

_DIMSPEC = {"NHWC": ("NHWC", "HWIO", "NHWC"),
            "NCHW": ("NCHW", "OIHW", "NCHW")}


def _conv(x, w, stride, layout):
    if layout == "GEMM":
        # 1x1/s1 only: the conv IS a matmul over flattened pixels
        b, h, w_, cin = x.shape
        cout = w.shape[-1]
        y = lax.dot_general(x.reshape(b * h * w_, cin),
                            w.reshape(cin, cout), (((1,), (0,)), ((), ())))
        return y.reshape(b, h, w_, cout)
    k = w.shape[0] if layout == "NHWC" else w.shape[2]
    pad = (k - 1) // 2
    # bf16 in/out (MXU accumulates f32 internally); an explicit f32
    # preferred_element_type would hand the backward a mixed-dtype conv
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=_DIMSPEC[layout])


def _sync(x):
    # host value fetch: the scalar cannot arrive before the device has
    # produced it, so it is a barrier on every runtime. (The pre-PR-1
    # CONV_PROBE_r05 rows were timed with a weaker sync and read above
    # the physical peak — only their NHWC-vs-NCHW relatives were
    # meaningful, and those were validated end to end.)
    leaf = jax.tree_util.tree_leaves(x)[0]
    float(jnp.sum(leaf.astype(jnp.float32)))


def _time(fn, args, iters):
    """Per-op device time with dispatch amortized: `iters` copies of the
    op run INSIDE one jitted program (inputs perturbed per copy so XLA
    cannot CSE them into one), one value-fetch sync at the end. A
    per-call loop would measure the per-dispatch host floor, not the
    sub-millisecond convs."""
    x, w = args

    def repeated(x, w):
        acc = None
        for i in range(iters):
            eps = jnp.asarray(i * 1e-6, x.dtype)  # keep the conv dtype
            y = fn(x + eps, w)
            acc = y if acc is None else acc + y
        return acc

    r = jax.jit(repeated)
    _sync(r(x, w))  # compile + warmup
    t0 = time.perf_counter()
    _sync(r(x, w))
    return (time.perf_counter() - t0) / iters


def probe(iters: int = 30):
    dev = jax.devices()[0]
    for name, b, h, w_, cin, cout, k, stride in SHAPES:
        flops = conv_unit_flops(b, h // stride, w_ // stride, cin, cout,
                                k, k)
        rs = np.random.RandomState(0)
        layouts = ["NHWC", "NCHW"]
        if k == 1 and stride == 1:
            layouts.append("GEMM")  # matmul spelling of the same conv
        for layout in layouts:
            if layout == "NCHW":
                x = jnp.asarray(rs.randn(b, cin, h, w_), jnp.bfloat16)
                kern = jnp.asarray(rs.randn(cout, cin, k, k), jnp.bfloat16)
            else:  # NHWC and GEMM share the NHWC operand layout
                x = jnp.asarray(rs.randn(b, h, w_, cin), jnp.bfloat16)
                kern = jnp.asarray(rs.randn(k, k, cin, cout), jnp.bfloat16)

            fwd = jax.jit(lambda a, c: _conv(a, c, stride, layout))
            loss = lambda a, c: jnp.sum(
                _conv(a, c, stride, layout).astype(jnp.float32))
            dgrad = jax.jit(jax.grad(loss, argnums=0))
            wgrad = jax.jit(jax.grad(loss, argnums=1))

            row = {"shape": name, "layout": layout,
                   "gflops": round(flops / 1e9, 1),
                   # geometry fields: apply_conv_probe.py --geom turns
                   # rows into per-geometry decisions (ops/conv2d.py)
                   "kh": k, "kw": k, "stride": [stride, stride],
                   "cin": cin, "cout": cout, "groups": 1,
                   "dilation": [1, 1], "dtype": "bfloat16",
                   "device": dev.device_kind}
            for pname, fn in (("fwd", fwd), ("dgrad", dgrad),
                              ("wgrad", wgrad)):
                dt = _time(fn, (x, kern), iters)
                row[f"{pname}_ms"] = round(dt * 1e3, 3)
                row[f"{pname}_tfs"] = round(flops / dt / 1e12, 2)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    probe(int(sys.argv[1]) if len(sys.argv) > 1 else 30)
