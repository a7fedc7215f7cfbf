"""Candidate timing for the autotuner — chip mode only.

Every routine here follows the timing rules of
scripts/flash_block_sweep.py: chain each timed call on the previous result
so executions cannot be elided or pipelined, and sync by FETCHING a scalar
to host — a sync that holds on every runtime, since the value cannot
arrive before the device has produced it.

These functions never run in dry mode (``autotune.dry_run()`` gates them),
so they may assume a real backend; candidate order is deterministic and a
candidate only wins on a strictly lower time, keeping ties stable across
runs.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Sequence, Tuple

__all__ = ["time_fn", "measure_flash_blocks", "measure_bn_row_block",
           "measure_fba_row_block", "measure_conv_layouts",
           "measure_conv_geom", "measure_grad_buckets",
           "measure_kv_page_tokens", "measure_quant_matmul",
           "CONV_PROBE_SHAPES"]

_WARMUP = 1
_ITERS = 3


def _sync(x) -> float:
    """Host-fetch barrier: the scalar cannot arrive before the device
    has finished producing it."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def time_fn(fn, *args, iters: int = _ITERS) -> float:
    """Milliseconds per call of ``fn(*args)``: compile+warmup outside the
    timed region, then ``iters`` chained calls closed by a host fetch.
    ``fn`` must return something tree-like whose first leaf has the shape
    of ``args[0]`` so calls can chain; non-chainable fns are re-invoked
    on the original args (still sync-fetched each sequence end)."""
    cur = fn(*args)
    _sync(cur)  # compile + warmup
    chain = (getattr(cur, "shape", None) == getattr(args[0], "shape", None)
             and getattr(cur, "dtype", None) == getattr(args[0], "dtype",
                                                        None))
    t0 = time.perf_counter()
    for _ in range(iters):
        cur = fn(cur, *args[1:]) if chain else fn(*args)
    _sync(cur)
    return (time.perf_counter() - t0) / iters * 1e3


def _pick(timed: Sequence[Tuple[dict, float]]) -> Tuple[dict, float]:
    """First strictly-fastest candidate in presentation order (stable under
    exact ties, so re-measuring identical timings re-picks identically)."""
    best, best_ms = timed[0]
    for cfg, ms in timed[1:]:
        if ms < best_ms:
            best, best_ms = cfg, ms
    return best, best_ms


def measure_flash_blocks(s_q: int, s_k: int, d: int, causal: bool,
                         dtype, candidates: Sequence[Tuple[int, int]]
                         ) -> Tuple[dict, float]:
    """Time fwd+bwd of the flash kernel per (block_q, block_k) candidate on
    a small fixed (b=1, h=8) problem of the target sequence geometry."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.attention_kernel import _flash

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 8, s_q, d), dtype)
    k = jax.random.normal(kk, (1, 8, s_k, d), dtype)
    v = jax.random.normal(kv, (1, 8, s_k, d), dtype)

    timed: List[Tuple[dict, float]] = []
    for bq, bk in candidates:
        def loss(q_, k_, v_, bq=bq, bk=bk):
            return jnp.sum(_flash(q_, k_, v_, causal, bq, bk)
                           .astype(jnp.float32))

        g = jax.jit(jax.grad(loss, argnums=0))
        ms = time_fn(g, q, k, v)
        timed.append(({"block_q": bq, "block_k": bk}, ms))
    return _pick(timed)


def measure_bn_row_block(rows: int, c: int, dtype,
                         candidates: Sequence[int]) -> Tuple[dict, float]:
    """Time the single-read BN stats kernel per row-block candidate on the
    exact (rows, C) shape being tuned."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.bn_kernel import bn_stats

    x = jax.random.normal(jax.random.PRNGKey(0), (rows, c), dtype)
    timed: List[Tuple[dict, float]] = []
    for rb in candidates:
        fn = jax.jit(functools.partial(bn_stats, row_block=rb))
        # bn_stats returns (sum, sumsq), not x-shaped: time_fn re-invokes
        ms = time_fn(fn, x)
        timed.append(({"row_block": rb}, ms))
    return _pick(timed)


def measure_fba_row_block(rows: int, c: int, dtype, relu: bool,
                          candidates: Sequence[int]) -> Tuple[dict, float]:
    """Time fwd+bwd of the FUSED BN block (stats+apply(+ReLU) forward,
    reductions+dx backward — ops/bn_kernel.fused_bn_apply_train) per
    row-block candidate on the exact (rows, C) shape being tuned. Both
    kernels share the decision, so the timed unit is a full grad step."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.bn_kernel import fused_bn_apply_train

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, c), dtype)
    gamma = jnp.ones((c,), jnp.float32)
    beta = jnp.zeros((c,), jnp.float32)

    timed: List[Tuple[dict, float]] = []
    for rb in candidates:
        def loss(x_, rb=rb):
            return jnp.sum(fused_bn_apply_train(
                x_, gamma, beta, 1e-5, relu, rb)[0].astype(jnp.float32))

        g = jax.jit(jax.grad(loss))
        ms = time_fn(g, x)  # grad is x-shaped: calls chain
        timed.append(({"row_block": rb}, ms))
    return _pick(timed)


def measure_grad_buckets(param_bytes: int, n_devices: int, dtype,
                         candidates: Sequence[int]) -> Tuple[dict, float]:
    """Time one full compressed all-reduce of ``param_bytes`` worth of
    f32 gradient per bucket-bound candidate, over the ambient device
    mesh's ``data`` axis via grad_comm's explicit shard_map psum path —
    the wire cost a training step pays, minus the backward it would
    overlap with (overlap headroom rises as buckets shrink; the measured
    total captures the per-collective latency the bound amortizes).
    Returns ({"bucket_bytes": best}, best_ms)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from bigdl_tpu.parallel.grad_comm import compressed_psum

    mode = "fp16" if np.dtype(dtype).name == "float16" else "bf16"
    devs = jax.devices()[:n_devices]
    mesh = Mesh(np.array(devs), ("data",))
    n_elems = max(1, int(param_bytes) // 4)

    timed: List[Tuple[dict, float]] = []
    for bound in candidates:
        per_bucket = max(1, int(bound) // 4)
        lens = [per_bucket] * (n_elems // per_bucket)
        if n_elems % per_bucket:
            lens.append(n_elems % per_bucket)

        def reduce_all(x, lens=lens, mesh=mesh, mode=mode):
            outs = []
            off = 0
            for ln in lens:
                stacked = jax.lax.dynamic_slice_in_dim(
                    x, off, ln * n_devices).reshape(n_devices, ln)
                outs.append(compressed_psum(stacked, mesh, "data", mode))
                off += ln * n_devices
            return jnp.concatenate(outs)

        x = jax.random.normal(jax.random.PRNGKey(0),
                              (n_elems * n_devices,), jnp.float32)
        fn = jax.jit(reduce_all)
        ms = time_fn(fn, x)  # output is not x-shaped: re-invokes
        timed.append(({"bucket_bytes": int(bound)}, ms))
    return _pick(timed)


# Representative conv shape set: the distinct ResNet-50 b32 bottleneck
# geometries (n, h, w, cin, cout, kh, kw, stride) — a scaled-down version
# of scripts/conv_bwd_probe.py's sweep so one measure pass stays cheap.
# Total ms across the set approximates one step's conv time, so summing is
# the right weighting for a single global per-pass decision.
CONV_PROBE_SHAPES: Tuple[Tuple[int, int, int, int, int, int, int, int], ...] = (
    (32, 224, 224, 3, 64, 7, 7, 2),    # stem (the measured 7x wgrad case)
    (32, 56, 56, 64, 64, 1, 1, 1),
    (32, 56, 56, 64, 64, 3, 3, 1),
    (32, 28, 28, 128, 128, 3, 3, 1),
    (32, 14, 14, 256, 256, 3, 3, 1),
    (32, 7, 7, 512, 512, 3, 3, 1),
)


def measure_conv_geom(pass_name: str, geom: tuple, x_shape: tuple,
                      candidates: Sequence[str]) -> Tuple[dict, float]:
    """Time ONE conv pass of ONE geometry under each candidate layout
    (NHWC/NCHW, plus GEMM where eligible) at the exact activation shape
    the training trace presented — the per-geometry refinement of
    :func:`measure_conv_layouts` (ISSUE 3). Returns ({"layout": best},
    best_ms); candidate order is the deterministic CONV_GEOM_LAYOUTS
    order, so exact ties re-pick identically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.conv2d import _conv_in_layout

    kh, kw, sh, sw, cin, cout, groups, dh, dw, dtype_name = geom
    n, h, w_ = int(x_shape[0]), int(x_shape[1]), int(x_shape[2])
    dtype = np.dtype(dtype_name)
    kx, kw_ = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, h, w_, cin), dtype)
    wgt = jax.random.normal(kw_, (kh, kw, cin // groups, cout), dtype)
    # SAME-style symmetric padding approximates the training sites (the
    # geometry key carries no padding; for the k=1 GEMM-eligible sites
    # this is exactly zero padding)
    pad = ((kh // 2, kh // 2), (kw // 2, kw // 2))

    timed: List[Tuple[dict, float]] = []
    for layout in candidates:
        conv = functools.partial(
            _conv_in_layout, stride=(sh, sw), padding=pad,
            rhs_dilation=(dh, dw), groups=groups, layout=layout)
        if pass_name == "fwd":
            fn = jax.jit(lambda x_, w_c=wgt, c=conv: c(x_, w_c))
            ms = time_fn(fn, x)
        else:
            dy = jnp.ones_like(conv(x, wgt))
            if pass_name == "dgrad":
                fn = jax.jit(lambda dy_, x_=x, w_c=wgt, c=conv:
                             jax.linear_transpose(
                                 lambda xx: c(xx, w_c), x_)(dy_)[0])
            else:
                fn = jax.jit(lambda dy_, x_=x, w_c=wgt, c=conv:
                             jax.linear_transpose(
                                 lambda ww: c(x_, ww), w_c)(dy_)[0])
            ms = time_fn(fn, dy)
        timed.append(({"layout": layout}, ms))
    return _pick(timed)


def measure_conv_layouts(dtype) -> Tuple[dict, float]:
    """Per-pass independent layout decision (the generalized form of
    scripts/conv_bwd_probe.py + ops/conv2d.decide_from_probe): time each
    of fwd/dgrad/wgrad under NHWC and NCHW across the shape set and pick
    the per-pass minimum of the totals. Returns ({'fwd'|'dgrad'|'wgrad':
    layout}, total_best_ms)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.conv2d import _conv_in_layout

    totals = {p: {"NHWC": 0.0, "NCHW": 0.0}
              for p in ("fwd", "dgrad", "wgrad")}
    for n, h, w, cin, cout, kh, kw, stride in CONV_PROBE_SHAPES:
        kx, kw_ = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (n, h, w, cin), dtype)
        wgt = jax.random.normal(kw_, (kh, kw, cin, cout), dtype)
        pad = ((kh // 2, kh // 2), (kw // 2, kw // 2))
        for layout in ("NHWC", "NCHW"):
            conv = functools.partial(
                _conv_in_layout, stride=(stride, stride), padding=pad,
                rhs_dilation=(1, 1), groups=1, layout=layout)
            y = conv(x, wgt)
            dy = jnp.ones_like(y)

            fwd = jax.jit(lambda x_, w_=wgt: conv(x_, w_))
            totals["fwd"][layout] += time_fn(fwd, x)

            dgrad = jax.jit(lambda dy_, x_=x, w_=wgt: jax.linear_transpose(
                lambda xx: conv(xx, w_), x_)(dy_)[0])
            totals["dgrad"][layout] += time_fn(dgrad, dy)

            wgrad = jax.jit(lambda dy_, x_=x, w_=wgt: jax.linear_transpose(
                lambda ww: conv(x_, ww), w_)(dy_)[0])
            totals["wgrad"][layout] += time_fn(wgrad, dy)

    decision: Dict[str, str] = {}
    best_total = 0.0
    for p, per in totals.items():
        # NHWC wins ties: deterministic, and it is the framework default
        lay = "NCHW" if per["NCHW"] < per["NHWC"] else "NHWC"
        decision[p] = lay
        best_total += per[lay]
    return decision, best_total


def measure_kv_page_tokens(max_len: int, kv_heads: int, head_dim: int,
                           dtype, candidates: Sequence[int]
                           ) -> Tuple[dict, float]:
    """Time one paged decode-step memory roundtrip per page-size
    candidate: gather a slot's pages into the contiguous view the decode
    graph reads, then scatter one token's K/V back — the two data
    movements paging adds to every step. Small pages pay index fan-out
    (max_len/pt gather rows), large pages pay transfer granularity; the
    sweet spot is the chip's to declare. Returns
    ({"page_tokens": best}, best_ms)."""
    import jax
    import jax.numpy as jnp

    timed: List[Tuple[dict, float]] = []
    for pt in candidates:
        mp = max_len // pt
        pool = jax.random.normal(
            jax.random.PRNGKey(0),
            (1 + mp, kv_heads, pt, head_dim)).astype(dtype)
        pages = jnp.arange(1, mp + 1, dtype=jnp.int32)
        tok = jnp.ones((kv_heads, head_dim), dtype)

        def roundtrip(pool, pages=pages, tok=tok, mp=mp, pt=pt):
            x = jnp.take(pool, pages, axis=0)
            view = x.transpose(1, 0, 2, 3).reshape(
                kv_heads, mp * pt, head_dim)
            # fold the view back in so the gather cannot be elided
            upd = tok + view[:, -1, :]
            return pool.at[pages[-1], :, pt - 1, :].set(upd)

        fn = jax.jit(roundtrip)
        ms = time_fn(fn, pool)  # pool-shaped output: calls chain
        timed.append(({"page_tokens": int(pt)}, ms))
    return _pick(timed)


def measure_quant_matmul(m: int, k: int, n: int, dtype
                         ) -> Tuple[dict, float]:
    """Time the two quantized-matmul spellings for one (m, k, n)
    activation/weight shape (ISSUE 17): the dequant-fused epilogue
    (``(x @ q.astype(dt)) * s``) vs the native int8 ``dot_general``
    with i32 accumulation plus the dynamic activation-quant prologue.
    Candidate order puts dequant first so exact ties keep the shipped
    default. Returns ({"kind": best}, best_ms)."""
    import jax
    import jax.numpy as jnp

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), dtype)
    q = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
    s = jnp.full((n,), 0.01, jnp.float32)

    def dequant(x_):
        return (x_ @ q.astype(x_.dtype)) * s.astype(x_.dtype)

    def native(x_):
        xf = x_.astype(jnp.float32)
        xs = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                         1e-8) / 127.0
        xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(x_.dtype) * xs.astype(x_.dtype) \
            * s.astype(x_.dtype)

    timed: List[Tuple[dict, float]] = []
    for kind, fn in (("dequant", dequant), ("native-int8", native)):
        jitted = jax.jit(fn)
        ms = time_fn(jitted, x)  # (m, n) output: re-invokes
        timed.append(({"kind": kind}, ms))
    return _pick(timed)
