"""Analytic matmul/conv FLOPs from a traced jaxpr.

The reference trusts its perf harness because the metric is simple and
auditable (records/second, DistriOptimizerPerf.scala:35-150). Our MFU
metric needs a FLOPs numerator that is equally auditable: XLA's
``compiled.cost_analysis()["flops"]`` is backend-dependent and opaque, so
we count FLOPs ourselves by walking the jaxpr of the (uncompiled) train
step and summing the two primitives where essentially all deep-learning
FLOPs live:

* ``dot_general``: 2 x batch x M x N x K
* ``conv_general_dilated``: 2 x |out| x (C_in/groups) x prod(kernel spatial)

Everything else (elementwise, reductions, layout) is bandwidth-bound on
TPU and excluded by convention — this is the standard "model FLOPs"
denominator used for MFU. Control-flow bodies are recursed into
(``scan`` multiplied by trip count, ``cond`` by the most expensive
branch); ``remat`` bodies are counted once (algorithmic FLOPs, not
executed FLOPs, per the usual MFU definition).

GEMM-path accounting (ISSUE 3): when the per-geometry conv policy runs a
1x1 stride-1 conv as ``dot_general`` over ``(N*H*W, Cin) x (Cin, Cout)``,
the contraction is unchanged — ``2*N*H*W*Cin*Cout`` FLOPs either way —
so the analytic numerator is invariant under the layout/GEMM choice; the
two primitive rules above agree by construction, and
:func:`conv_unit_flops` is the closed-form spelling shared by the probe
and roofline scripts so every TF/s figure in PERF.md uses one numerator.
"""

from __future__ import annotations

import math

import jax
from jax.extend import core as jex_core


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= float(x)
    return out


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = _prod(lhs[i] for i in lb)
        contract = _prod(lhs[i] for i in lc)
        m = _prod(lhs[i] for i in range(len(lhs))
                  if i not in lb and i not in lc)
        rb, rcs = set(_rb), set(rc)
        n = _prod(rhs[i] for i in range(len(rhs))
                  if i not in rb and i not in rcs)
        return 2.0 * batch * m * n * contract
    if name == "conv_general_dilated":
        out_shape = eqn.outvars[0].aval.shape
        kernel = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        k_spatial = _prod(kernel[d] for d in dn.rhs_spec[2:])
        cin_per_group = float(kernel[dn.rhs_spec[1]])
        macs = _prod(out_shape) * cin_per_group * k_spatial
        # input dilation (the autodiff dgrad of a STRIDED conv) inserts
        # stride-1 zeros between input elements; only 1/prod(lhs_dilation)
        # of kernel taps hit data, the rest multiply structural zeros.
        # Without this the ViT patchify's (stride-16) backward counted
        # 256x its real MACs and inflated MFU past the physical ceiling
        # (caught by the HLO cross-check, PERF.md §8.2). The algorithmic
        # invariant this restores: dgrad MACs == wgrad MACs == fwd MACs
        # (transposes of the same linear map have identical nnz).
        ld = eqn.params.get("lhs_dilation") or ()
        d = _prod(ld)
        if d > 1:
            macs /= d
        return 2.0 * macs
    return 0.0


def _sub_jaxprs(params):
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, jex_core.ClosedJaxpr):
                    yield w.jaxpr
                elif isinstance(w, jex_core.Jaxpr):
                    yield w


def jaxpr_flops_by_kind(jaxpr) -> dict:
    """Like :func:`jaxpr_flops` but split by primitive family:
    ``{"matmul": f, "conv": f}``. ``dot_general`` (and Pallas kernels
    with an author-declared CostEstimate — their declared FLOPs are MXU
    dot FLOPs by construction, PERF.md §5) count as matmul;
    ``conv_general_dilated`` as conv."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = {"matmul": 0.0, "conv": 0.0}

    def add(dst, src, mult=1.0):
        dst["matmul"] += mult * src["matmul"]
        dst["conv"] += mult * src["conv"]

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        f = _eqn_flops(eqn)
        if f:
            total["conv" if name == "conv_general_dilated"
                  else "matmul"] += f
        if name == "cond":
            branches = [jaxpr_flops_by_kind(b)
                        for b in eqn.params["branches"]]
            if branches:
                add(total, max(branches,
                               key=lambda d: d["matmul"] + d["conv"]))
            continue
        mult = 1.0
        if name == "scan":
            mult = float(eqn.params.get("length", 1))
        elif name == "pallas_call":
            ce = eqn.params.get("cost_estimate")
            if ce is not None and getattr(ce, "flops", 0):
                total["matmul"] += float(ce.flops)
                continue
            gm = eqn.params.get("grid_mapping")
            grid = getattr(gm, "grid", ()) or ()
            if all(isinstance(g, int) for g in grid):
                mult = _prod(grid) if grid else 1.0
        for sub in _sub_jaxprs(eqn.params):
            add(total, jaxpr_flops_by_kind(sub), mult)
    return total


def fn_flops_by_kind(fn, *args, **kwargs) -> dict:
    """Matmul/conv FLOPs split of ``fn(*args, **kwargs)`` (abstract
    trace); same recursion rules as :func:`fn_flops`."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return jaxpr_flops_by_kind(closed)


def jaxpr_flops(jaxpr) -> float:
    """Total matmul+conv FLOPs of one evaluation of ``jaxpr``."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        name = eqn.primitive.name
        if name == "cond":
            total += max((jaxpr_flops(b) for b in eqn.params["branches"]),
                         default=0.0)
            continue
        mult = 1.0
        if name == "scan":
            mult = float(eqn.params.get("length", 1))
        elif name == "while":
            # trip count is dynamic; count the body once (lower bound)
            mult = 1.0
        elif name == "pallas_call":
            # Without special handling the kernel jaxpr is counted ONCE
            # though it runs once per grid program — flash attention's
            # seq^2 inner products were invisible and long-context MFU
            # wildly undercounted (found at seq 16k: analytic step FLOPs
            # equalled the 1k config's). Preference order:
            #  1. an author-declared CostEstimate (our flash kernels set
            #     ALGORITHMIC flops: causal-skip-aware, backward score
            #     recomputation excluded — comparable to dense autodiff);
            #  2. grid-size x kernel-body as a fallback for kernels
            #     without an estimate (counts recomputation and masked
            #     grid cells as written).
            ce = eqn.params.get("cost_estimate")
            if ce is not None and getattr(ce, "flops", 0):
                total += float(ce.flops)
                continue
            gm = eqn.params.get("grid_mapping")
            grid = getattr(gm, "grid", ()) or ()
            if all(isinstance(g, int) for g in grid):
                mult = _prod(grid) if grid else 1.0
        for sub in _sub_jaxprs(eqn.params):
            total += mult * jaxpr_flops(sub)
    return total


def conv_unit_flops(n: int, h_out: int, w_out: int, cin: int, cout: int,
                    kh: int, kw: int, groups: int = 1) -> float:
    """Closed-form 2·MAC FLOPs of ONE conv pass (fwd == dgrad == wgrad:
    transposes of the same linear map have identical nnz). The 1x1 GEMM
    spelling computes the identical contraction, so this is also its
    dot_general count — one numerator for probe/roofline TF/s."""
    return 2.0 * n * h_out * w_out * cout * (cin / max(1, groups)) * kh * kw


def fn_flops(fn, *args, **kwargs) -> float:
    """Matmul+conv FLOPs of ``fn(*args, **kwargs)`` via abstract tracing."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return jaxpr_flops(closed)
