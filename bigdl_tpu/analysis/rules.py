"""tpulint rule registry — TPU perf/correctness anti-patterns caught at
trace time on CPU, before a chip is ever touched (ISSUE 4 tentpole).

Two kinds of rules share one catalog:

* **jaxpr rules** (:func:`run_jaxpr_rules`) walk the traced ClosedJaxpr
  of a train/eval step — every nested jit/custom_vjp/pallas_call level —
  and fire on equation-level evidence: bf16→f32 upcasts re-reading large
  activations, scalar captures that promote a bf16 path, un-donated step
  buffers (~2x HBM), Pallas blocks that violate the Mosaic minimum-tile
  rules or pad their arrays, per-kernel VMEM working sets near the
  budget, and host callbacks inside the step.
* **module rules** (:func:`run_module_rules`) walk the model tree with
  the kernel/eligibility metadata PRs 1–3 already expose
  (``ops/conv2d.resolve_site_layouts``, ``ops/bn_kernel`` tileability,
  ``ops/attention_kernel.flash_block_plan``) and fire on configuration:
  BN sites eligible for the fused apply block running unfused, GEMM-
  eligible 1x1 convs resolving to a spatial layout, channel/head dims
  off the 128-lane grid, ragged sequences that knock attention off the
  flash kernel.

Every finding carries rule id, family, severity, provenance and a fix
hint (:mod:`bigdl_tpu.analysis.report`). Severity policy: **error** =
measured-regression configs and compile-on-chip hazards (unfused
apply-eligible BN, illegal/padded Pallas tiles, ragged-seq kernel
fallback, host sync in the step) — ``--lint=strict`` refuses to launch
on these; **warning** = costs worth a look (missing donation, large
upcasts, VMEM pressure, GEMM opportunity); **info** = grid-fit notes.

The shared tile checkers (:func:`check_block_tiling`,
:func:`assert_blocks_tileable`) are also THE source of truth the kernel
tests assert through — previously each test file carried its own copy of
the (8,128)/(16,128) modulus asserts (ISSUE 4 satellite).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.analysis.jaxpr_walk import (aval_bytes, consumers_map,
                                           iter_levels, pallas_block_views,
                                           pallas_kernel_name,
                                           pallas_scratch_avals)
from bigdl_tpu.analysis.report import Finding, Report

__all__ = ["CATALOG", "run_jaxpr_rules", "run_module_rules",
           "run_comm_rules", "run_memory_rules", "run_decode_rules",
           "run_serving_tp_rules",
           "check_block_tiling", "check_block_padding",
           "assert_blocks_tileable", "min_sublane",
           "UPCAST_MIN_BYTES", "DONATE_MIN_BYTES", "VMEM_BUDGET_BYTES",
           "COMM_F32_MIN_BYTES", "COMM_MAX_COLLECTIVES",
           "HBM_WARN_FRAC", "SERVING_TP_MIN_BYTES"]

# rule id -> (family, severity, one-line catalog description)
CATALOG: Dict[str, Tuple[str, str, str]] = {
    "dtype-upcast": (
        "dtype", "warning",
        "large bf16→f32 convert feeding a leading-axis reduction or a "
        "matmul/conv — the activation crosses HBM again at 2x width "
        "(the unfused-BN stats pattern, PERF.md §2)"),
    "dtype-weak-scalar": (
        "dtype", "warning",
        "a captured f32 scalar promotes a large bf16 tensor to f32 — "
        "use a python scalar or cast the constant to bf16"),
    "donate-missing": (
        "donation", "warning",
        "jitted step keeps non-donated input buffers whose shape/dtype "
        "match outputs (params/opt-state round-trip) — ~2x HBM for the "
        "train state"),
    "donate-ok": (
        "donation", "info",
        "step donates its round-tripping buffers (the "
        "optim/optimizer.py + parallel/data_parallel.py contract)"),
    "tile-min": (
        "tiling", "error",
        "Pallas block violates the Mosaic minimum-tile rule "
        "((8,128) f32 / (16,128) bf16 / (32,128) int8, or block dim == "
        "array dim) — lowers in interpret mode, compile-fails on chip"),
    "tile-pad": (
        "tiling", "error",
        "Pallas block does not divide its array dim — Mosaic pads every "
        "block and the kernel burns the padding fraction (the s=768 "
        "q-block case, ADVICE r5 #2)"),
    "tile-ragged-attn": (
        "tiling", "error",
        "sequence not lane-tileable — attention silently leaves the "
        "flash kernel for the remat-scan/dense fallback"),
    "tile-bn-ineligible": (
        "tiling", "info",
        "BN site cannot take the single-read kernel (C % 128 != 0); the "
        "jnp path re-reads the activation per pass"),
    "vmem-budget": (
        "tiling", "warning",
        "per-program VMEM working set (double-buffered blocks + scratch) "
        "near the ~16 MiB budget — spills or compile failure on chip"),
    "tile-seq-clamp": (
        "tiling", "info",
        "sequence clamps the flash blocks below the 512 default (the "
        "s=768 fix: 256-blocks instead of padded 1024-blocks)"),
    "fusion-bn-unfused": (
        "fusion", "error",
        "BatchNormalization site eligible for the fused apply block "
        "(fused='apply', PERF.md §10) is running unfused/stats — the "
        "measured-regression config"),
    "tile-bn-fallback": (
        "tiling", "warning",
        "fused BN requested but sites fell back to the jnp path (rows "
        "not tileable at this batch) — the fusion silently isn't "
        "happening"),
    "fusion-conv-gemm": (
        "fusion", "warning",
        "GEMM-eligible 1x1/s1 conv resolves to a spatial layout — "
        "lax.dot_general lowering available (PERF.md §11)"),
    "fusion-attn-dense": (
        "fusion", "info",
        "attention runs the dense XLA path; the Pallas flash kernel is "
        "available (attn_impl='flash')"),
    "layout-c128": (
        "layout", "info",
        "feature dims off the 128-lane grid — MXU tiles are padded, "
        "waste estimated via utils/flops.conv_unit_flops"),
    "layout-headdim": (
        "layout", "info",
        "attention head_dim is not a multiple of 128 — the MXU "
        "contracts over it half-filled (hd128 A/B: +24% tok/s, "
        "PERF.md §8.2)"),
    "host-sync": (
        "host-sync", "error",
        "host callback inside the step — every dispatch round-trips "
        "through the host and stalls the device pipeline"),
    "comm-f32-allreduce": (
        "comm", "warning",
        "multi-device strategy reduces >=1 MiB gradient buckets in f32 "
        "with compression off — twice the wire bytes the 16-bit codec "
        "path (--gradCompress bf16) would move"),
    "comm-unbucketed": (
        "comm", "warning",
        "gradient reduction is per-leaf (>16 collectives in one step "
        "graph / unbucketed grad tree) — per-collective launch latency "
        "is paid per parameter instead of per dense bucket"),
    "hbm-oversubscribed": (
        "memory", "error",
        "the compiled step's working set (obs/memory.build_plan) "
        "exceeds the device HBM — the run will RESOURCE_EXHAUST on "
        "first dispatch; caught pre-compile on CPU"),
    "hbm-tight": (
        "memory", "warning",
        "the compiled step's working set is within 15% of the device "
        "HBM — fragmentation or a live-buffer spike will tip it over "
        "(obs/memory forecasts the max batch that still fits)"),
    "lint-trace-error": (
        "meta", "info",
        "the step could not be traced; only module-level rules ran"),
    "decode-sampling-sort": (
        "decode", "warning",
        "full-vocab sort inside the per-token decode step — top-k/top-p "
        "warping pays O(V log V) per slot per token; at large vocab the "
        "sampler dominates the step (serve only the sort-free program "
        "to greedy/temperature traffic, or filter on a partial "
        "threshold)"),
    "kv-page-misfit": (
        "decode", "warning",
        "KV page token size misfits the layout: off the 8-sublane grid "
        "every pool page pads its tile, and when neither the flash "
        "block_k nor the page divides the other, K blocks straddle "
        "page boundaries in the gathered view (kv_page_plan)"),
    "quant-dequant-upcast": (
        "dtype", "error",
        "a dequantized int8/fp8 weight is re-materialized as f32 "
        "feeding a matmul whose other operand was upcast from bf16 — "
        "the dequant epilogue defeats the 8-bit storage AND drags the "
        "activation to f32; dequantize into the activation dtype "
        "instead (quant._QView does)"),
    "serving-unsharded-matmul": (
        "serving", "error",
        "tp-strategy serving graph carries a >=1 MiB matmul weight with "
        "fully-replicated placement — every chip runs the full matmul "
        "and tp buys nothing for it (a megatron_specs divisibility gate "
        "fell back to replication); alias of the mesh-aware "
        "shard-replicated-operand rule (ISSUE 19), kept for stable "
        "serve --lint output"),
}

UPCAST_MIN_BYTES = 2 * 1024 * 1024    # ignore small/scalar converts
DONATE_MIN_BYTES = 1 * 1024 * 1024    # per-buffer floor for the HBM rule
VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # ~16 MB/core (pallas_guide.md)
VMEM_WARN_FRAC = 0.8
COMM_F32_MIN_BYTES = 1 * 1024 * 1024  # grad wire worth compressing
COMM_MAX_COLLECTIVES = 16             # per-leaf-reduce smell threshold
HBM_WARN_FRAC = 0.85                  # plan/HBM ratio that earns hbm-tight
DECODE_SORT_MIN_LANES = 16384         # vocab size where the warp sort bites
SERVING_TP_MIN_BYTES = 1 * 1024 * 1024  # matmul weight worth sharding

_SUBLANE = {4: 8, 2: 16, 1: 32}


def min_sublane(*dtypes) -> int:
    """Mosaic's minimum sublane count across dtypes (8 for 4-byte, 16
    for bf16, 32 for int8) — shared with ops/bn_kernel's private copy."""
    need = 8
    for d in dtypes:
        need = max(need, _SUBLANE.get(np.dtype(d).itemsize, 8))
    return need


def _finding(rule: str, message: str, where: str = "", hint: str = "",
             detail: Optional[dict] = None,
             severity: Optional[str] = None) -> Finding:
    family, default_sev, _ = CATALOG[rule]
    return Finding(rule=rule, family=family,
                   severity=severity or default_sev, message=message,
                   where=where, hint=hint, detail=detail or {})


# ======================================================== shared checkers
def check_block_tiling(block_shape: Sequence, array_shape: Sequence,
                       dtype=np.float32) -> List[str]:
    """Problems (empty = legal) with ONE Pallas block against the Mosaic
    tiling rules: over the last two dims, the lane dim must be a multiple
    of 128 or equal the array dim, and the sublane dim a multiple of the
    dtype's minimum (8/16/32) or equal the array dim. The single source
    of truth the kernel tests assert through (previously copied per test
    file)."""
    probs: List[str] = []
    bs, ashape = tuple(block_shape), tuple(array_shape)
    if len(bs) < 1 or len(ashape) < 1:
        return probs
    pairs = list(zip(bs[-2:], ashape[-2:]))
    if not all(isinstance(b, (int, np.integer)) and
               isinstance(a, (int, np.integer)) for b, a in pairs):
        return probs  # squeezed/symbolic dims: nothing to check
    b_lane, a_lane = pairs[-1]
    if not (b_lane == a_lane or b_lane % 128 == 0):
        probs.append(f"lane dim {b_lane} not %128 and != array dim "
                     f"{a_lane}")
    if len(pairs) == 2:
        ms = min_sublane(dtype)
        b_sub, a_sub = pairs[0]
        if not (b_sub == a_sub or b_sub % ms == 0):
            probs.append(f"sublane dim {b_sub} not %{ms} "
                         f"(dtype {np.dtype(dtype).name}) and != array "
                         f"dim {a_sub}")
    return probs


def check_block_padding(block_shape: Sequence, array_shape: Sequence
                        ) -> float:
    """Padding-waste fraction (0.0 = none) a block induces over the last
    two dims: Mosaic rounds each dim up to a whole number of blocks."""
    real, padded = 1.0, 1.0
    for b, a in zip(tuple(block_shape)[-2:], tuple(array_shape)[-2:]):
        if not (isinstance(b, (int, np.integer)) and
                isinstance(a, (int, np.integer))) or b <= 0 or a <= 0:
            return 0.0
        real *= a
        padded *= -(-a // b) * b
    return 0.0 if padded <= real else 1.0 - real / padded


def assert_blocks_tileable(pairs: Iterable[Tuple[Sequence, Sequence]],
                           dtype=np.float32) -> None:
    """Raise AssertionError listing every (block, array) pair that fails
    :func:`check_block_tiling` — the spelling the kernel tests use."""
    bad = []
    for bs, ashape in pairs:
        probs = check_block_tiling(bs, ashape, dtype)
        if probs:
            bad.append((tuple(bs), tuple(ashape), probs))
    assert not bad, f"Mosaic-illegal blocks: {bad}"


# =========================================================== jaxpr rules
_REDUCE_PRIMS = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod")
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
_BINARY_PRIMS = ("add", "sub", "mul", "div", "max", "min", "pow")
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed")


def _dtype_name(aval) -> str:
    d = getattr(aval, "dtype", None)
    return np.dtype(d).name if d is not None else ""


def _rule_dtype_upcast(levels, report: Report) -> None:
    hits = []
    total = 0
    for lv in levels:
        cmap = consumers_map(lv.jaxpr)
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "convert_element_type":
                continue
            if _dtype_name(eqn.invars[0].aval) != "bfloat16":
                continue
            if np.dtype(eqn.params.get("new_dtype")).name != "float32":
                continue
            out = eqn.outvars[0]
            b = aval_bytes(out.aval)
            if b < UPCAST_MIN_BYTES:
                continue
            cons = cmap.get(out, [])
            interesting = False
            for c in cons:
                if c.primitive.name in _MATMUL_PRIMS:
                    interesting = True
                elif c.primitive.name in _REDUCE_PRIMS:
                    nd = len(getattr(out.aval, "shape", ()))
                    axes = tuple(c.params.get("axes", ()))
                    # leading-axis reductions are the BN-stats pattern;
                    # a last-axis reduce is the (expected) fp32 softmax
                    if nd and (nd - 1) not in axes:
                        interesting = True
            if interesting:
                hits.append(lv.where(i, eqn))
                total += b
    if hits:
        report.add(_finding(
            "dtype-upcast",
            f"{len(hits)} bf16→f32 upcast(s) totalling "
            f"{total / 2**20:.0f} MiB feed leading-axis reductions or "
            "matmuls — the activation crosses HBM again at 2x width",
            where="; ".join(hits[:4]) + ("…" if len(hits) > 4 else ""),
            hint="fuse the consumer (e.g. --fusedBN apply keeps the "
                 "upcast inside one kernel) or keep the chain in bf16",
            detail={"count": len(hits), "bytes": total,
                    "sites": hits[:16]}))


def _rule_weak_scalar(levels, report: Report) -> None:
    """Type promotion inserts the upcast BEFORE the mixing op, so the
    pattern in the jaxpr is: convert(bf16→f32) whose consumer is a
    binary elementwise op against a STRONG f32 scalar (an np.float32
    constant captured from python; a plain python scalar stays weak and
    never forces the promotion)."""
    hits = []
    for lv in levels:
        cmap = consumers_map(lv.jaxpr)
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "convert_element_type":
                continue
            if _dtype_name(eqn.invars[0].aval) != "bfloat16":
                continue
            if np.dtype(eqn.params.get("new_dtype")).name != "float32":
                continue
            out = eqn.outvars[0]
            if aval_bytes(out.aval) < UPCAST_MIN_BYTES:
                continue
            for c in cmap.get(out, []):
                if c.primitive.name not in _BINARY_PRIMS \
                        or len(c.invars) != 2:
                    continue
                other = (c.invars[0] if c.invars[1] is out
                         else c.invars[1])
                oav = getattr(other, "aval", None)
                if getattr(oav, "shape", None) == () \
                        and _dtype_name(oav) == "float32":
                    hits.append(lv.where(i, eqn))
                    break
    if hits:
        report.add(_finding(
            "dtype-weak-scalar",
            f"{len(hits)} op(s) promote a large bf16 tensor to f32 via "
            "a captured f32 scalar",
            where="; ".join(hits[:4]) + ("…" if len(hits) > 4 else ""),
            hint="use a plain python scalar (weak-typed, stays bf16) or "
                 "cast the constant to the tensor dtype",
            detail={"count": len(hits), "sites": hits[:16]}))


def _rule_donation(closed, report: Report) -> None:
    """Top-level jit eqns only: the traced step itself (nested jits
    don't round-trip the train state)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "jit":
            continue
        donated = eqn.params.get("donated_invars")
        if not donated:
            donated = (False,) * len(eqn.invars)
        out_counts: Dict[tuple, int] = {}
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", None)
            key = (shape, _dtype_name(v.aval))
            out_counts[key] = out_counts.get(key, 0) + 1
        missing = donated_bytes = 0
        n_missing = 0
        for v, d in zip(eqn.invars, donated):
            b = aval_bytes(getattr(v, "aval", None))
            if d:
                donated_bytes += b
                continue
            key = (getattr(v.aval, "shape", None), _dtype_name(v.aval))
            if b >= DONATE_MIN_BYTES and out_counts.get(key, 0) > 0:
                out_counts[key] -= 1
                missing += b
                n_missing += 1
        name = eqn.params.get("name") or "step"
        if missing:
            report.add(_finding(
                "donate-missing",
                f"jit:{name} keeps {n_missing} non-donated buffer(s) "
                f"({missing / 2**20:.0f} MiB) whose shape/dtype "
                "round-trip to outputs — params/opt-state live twice "
                "in HBM",
                where=f"jit:{name}#{i}",
                hint="jax.jit(step, donate_argnums=(0, 1, 2)) — the "
                     "optim/optimizer.py:394 / data_parallel.py:180 "
                     "entry points already do",
                detail={"bytes": missing, "buffers": n_missing}))
        elif donated_bytes:
            report.add(_finding(
                "donate-ok",
                f"jit:{name} donates {donated_bytes / 2**20:.0f} MiB "
                "of round-tripping train state",
                where=f"jit:{name}#{i}",
                detail={"bytes": donated_bytes}))


def _rule_pallas(levels, report: Report) -> None:
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "pallas_call":
                continue
            where = lv.where(i, eqn)
            kname = pallas_kernel_name(eqn) or "pallas_call"
            views = pallas_block_views(eqn)
            tile_probs, pad_notes = [], []
            block_bytes = 0
            for bs, ashape, dtype, is_out in views:
                ints = [int(d) for d in bs
                        if isinstance(d, (int, np.integer))]
                block_bytes += int(np.prod(ints or [0])) * dtype.itemsize
                for p in check_block_tiling(bs, ashape, dtype):
                    tile_probs.append(f"block {tuple(bs)} on "
                                      f"{tuple(ashape)}: {p}")
                waste = check_block_padding(bs, ashape)
                if waste > 0.0:
                    pad_notes.append(
                        f"block {tuple(bs)} pads {tuple(ashape)} "
                        f"({waste * 100:.0f}% wasted)")
            if tile_probs:
                report.add(_finding(
                    "tile-min",
                    f"kernel {kname}: {len(tile_probs)} Mosaic-illegal "
                    f"block(s): {tile_probs[0]}",
                    where=where,
                    hint="use a (>=min-sublane, >=128) tile or make the "
                         "block dim equal the array dim",
                    detail={"problems": tile_probs}))
            if pad_notes:
                report.add(_finding(
                    "tile-pad",
                    f"kernel {kname}: {pad_notes[0]}",
                    where=where,
                    hint="clamp the block to a divisor of the array dim "
                         "(ops/attention_kernel._clamp_block is the "
                         "pattern) or pad the data once at the edge",
                    detail={"padded": pad_notes}))
            scratch = sum(aval_bytes(a) for a in pallas_scratch_avals(eqn))
            # streamed in/out blocks are double-buffered by Pallas;
            # scratch is single-instance
            working_set = 2 * block_bytes + scratch
            if working_set > VMEM_WARN_FRAC * VMEM_BUDGET_BYTES:
                report.add(_finding(
                    "vmem-budget",
                    f"kernel {kname}: ~{working_set / 2**20:.1f} MiB "
                    f"VMEM working set (budget ~"
                    f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB)",
                    where=where,
                    hint="shrink the block sizes (--autotune measure "
                         "searches the legal grid)",
                    detail={"bytes": working_set,
                            "block_bytes": block_bytes,
                            "scratch_bytes": scratch}))


# explicit cross-device reduction primitives (shard_map/pmap graphs —
# jit-SPMD traces carry none; the partitioner inserts those later, which
# is what run_comm_rules covers at the config level)
_COLLECTIVE_PRIMS = ("psum", "psum_invariant", "ppermute", "all_gather",
                     "all_to_all", "reduce_scatter", "psum_scatter",
                     "pmax", "pmin")


def _rule_collectives(levels, report: Report) -> None:
    """Count explicit collective eqns in the step graph: more than
    COMM_MAX_COLLECTIVES means the reduction is per-leaf — the dense-
    bucket accumulation grad_comm does (and the reference's partitioned
    all-reduce did) amortizes that launch latency away."""
    hits = []
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name in _COLLECTIVE_PRIMS:
                hits.append(lv.where(i, eqn))
    if len(hits) > COMM_MAX_COLLECTIVES:
        report.add(_finding(
            "comm-unbucketed",
            f"{len(hits)} collective op(s) in one step graph (threshold "
            f"{COMM_MAX_COLLECTIVES}) — per-leaf reduction pays launch "
            "latency per parameter",
            where="; ".join(hits[:4]) + ("…" if len(hits) > 4 else ""),
            hint="bucket the grads into dense size-bounded buffers "
                 "(parallel/grad_comm; --gradCompress enables it)",
            detail={"count": len(hits), "sites": hits[:16]}))


def _rule_host_sync(levels, report: Report) -> None:
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name in _CALLBACK_PRIMS:
                report.add(_finding(
                    "host-sync",
                    f"{eqn.primitive.name} inside the step — the "
                    "dispatch stalls on a host round-trip every "
                    "iteration",
                    where=lv.where(i, eqn),
                    hint="move host I/O outside the jitted step (log "
                         "from returned scalars; debug prints only "
                         "under a debug flag)"))


# prims a dequant chain routes through between the convert and the
# matmul: the scale multiply/add, layout moves, and the converts
# themselves — anything else breaks the chain (it's no longer "the
# dequantized weight", it's a computed tensor)
_DEQUANT_PASSTHRU = ("convert_element_type", "mul", "add", "transpose",
                     "reshape", "broadcast_in_dim")
_QUANT_SRC_DTYPES = ("int8", "float8_e4m3fn", "float8_e5m2")


def _convert_sources(var, produced_by, max_depth: int = 8) -> set:
    """Source dtype names of every convert_element_type on ``var``'s
    producer chain, walking back through :data:`_DEQUANT_PASSTHRU`
    prims only (bounded depth — dequant epilogues are shallow)."""
    out: set = set()
    stack = [(var, 0)]
    seen: set = set()
    while stack:
        v, d = stack.pop()
        if d > max_depth or id(v) in seen:
            continue
        seen.add(id(v))
        eqn = produced_by.get(id(v))
        if eqn is None or eqn.primitive.name not in _DEQUANT_PASSTHRU:
            continue
        if eqn.primitive.name == "convert_element_type":
            out.add(_dtype_name(eqn.invars[0].aval))
        for iv in eqn.invars:
            if getattr(iv, "count", None) is not None:  # Var, not Literal
                stack.append((iv, d + 1))
    return out


def _rule_quant_dequant_upcast(levels, report: Report) -> None:
    """ISSUE 17: a dot_general where one operand traces back to an
    int8/fp8 -> wide convert (the dequant) AND the other to a bf16 ->
    f32 convert means the epilogue was folded in f32 — the matmul runs
    at 2x the activation width for no accuracy reason. The quant module
    dequantizes into the ACTIVATION dtype, which never hits this."""
    hits = []
    for lv in levels:
        produced_by = {}
        for eqn in lv.jaxpr.eqns:
            for ov in eqn.outvars:
                produced_by[id(ov)] = eqn
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "dot_general":
                continue
            if len(eqn.invars) < 2:
                continue
            srcs = [_convert_sources(v, produced_by)
                    for v in eqn.invars[:2]]
            for a, b in ((0, 1), (1, 0)):
                if (any(s in _QUANT_SRC_DTYPES for s in srcs[a])
                        and "bfloat16" in srcs[b]
                        and _dtype_name(eqn.invars[b].aval)
                        == "float32"):
                    hits.append(lv.where(i, eqn))
                    break
    if hits:
        report.add(_finding(
            "quant-dequant-upcast",
            f"{len(hits)} matmul(s) pair a f32-rematerialized "
            "dequantized weight with a bf16-upcast activation — the "
            "contraction runs at f32 width, defeating both the 8-bit "
            "storage and the bf16 compute dtype",
            where="; ".join(hits[:4]) + ("…" if len(hits) > 4 else ""),
            hint="dequantize into the activation dtype "
                 "(w.astype(x.dtype), the serving/quant.py epilogue) "
                 "or take the native int8 dot_general path",
            detail={"count": len(hits), "sites": hits[:16]}))


def _rule_decode_sort(levels, report: Report) -> None:
    for lv in levels:
        for i, eqn in enumerate(lv.jaxpr.eqns):
            if eqn.primitive.name != "sort":
                continue
            aval = eqn.invars[0].aval
            lanes = int(aval.shape[-1]) if getattr(aval, "shape", ()) \
                else 0
            if lanes >= DECODE_SORT_MIN_LANES:
                report.add(_finding(
                    "decode-sampling-sort",
                    f"sort over {lanes} lanes in the decode step "
                    "(top-k/top-p warp) — O(V log V) per slot per "
                    "token",
                    where=lv.where(i, eqn),
                    hint="route greedy/temperature-only traffic "
                         "through the sort-free step program (the "
                         "engine picks per round); consider a "
                         "threshold-filter sampler at this vocab",
                    detail={"lanes": lanes}))


def run_decode_rules(closed=None, *, page_tokens: Optional[int] = None,
                     max_len: Optional[int] = None,
                     head_dim: Optional[int] = None, dtype=None,
                     report: Optional[Report] = None) -> Report:
    """Decode-hot-path rules (ISSUE 14), run by the serve preflight
    before the first request: equation-level anti-patterns in the traced
    decode step (``DecodeEngine.trace_step_jaxpr()``) — host callbacks
    (error: a per-token host round-trip caps tokens/s at the host
    latency) and full-vocab sampling sorts (warning) — plus the static
    page-layout fit against the flash block plan when paging is on."""
    report = report if report is not None else Report()
    if closed is not None:
        levels = list(iter_levels(closed))
        _rule_host_sync(levels, report)
        _rule_decode_sort(levels, report)
    if page_tokens and max_len and head_dim:
        from bigdl_tpu.ops.attention_kernel import kv_page_plan
        plan = kv_page_plan(page_tokens, max_len, head_dim,
                            dtype if dtype is not None else np.float32)
        problems = []
        if not plan["sublane_ok"]:
            problems.append(f"page_tokens {page_tokens} % "
                            f"{plan.get('sublane', 8)} != 0 "
                            "(padded sublanes on every pool page)")
        if not plan["block_aligned"]:
            problems.append(
                f"page_tokens {page_tokens} vs flash block_k "
                f"{plan['block_k']}: neither divides the other — K "
                "blocks straddle page boundaries")
        if problems:
            report.add(_finding(
                "kv-page-misfit", "; ".join(problems),
                where=f"kv_pages(page_tokens={page_tokens}, "
                      f"max_len={max_len})",
                hint="pick --kvPageTokens from the tuned ladder "
                     "(tuning.kv_page_tokens: 32/64/128/256, 8-aligned "
                     "and block-commensurate) or 'auto'",
                detail=plan))
    return report


def run_serving_tp_rules(params, n_shard: int,
                         report: Optional[Report] = None) -> Report:
    """Tensor-parallel serving placement rules (ISSUE 16), run by the
    serve preflight when ``--strategy tp:K`` (K > 1) is active, over the
    PLACED param tree (leaves are committed ``jax.Array``s carrying
    their sharding). Like :func:`run_comm_rules`, this reads placement
    rather than the jaxpr: jit-SPMD traces carry no sharding eqns, but
    the committed weights ARE the serving graph's matmul operands — a
    >=1 MiB weight matrix left fully replicated under tp means every
    chip runs that matmul whole (a ``megatron_specs`` divisibility gate
    fell back), which is exactly the perf bug worth refusing to serve.

    Since ISSUE 19 this is an alias of the mesh-aware
    :func:`bigdl_tpu.analysis.sharding_rules.run_replicated_operand_rules`
    (training + serving, any mesh), kept so the serve preflight output
    and its tests stay byte-stable."""
    from bigdl_tpu.analysis.sharding_rules import \
        run_replicated_operand_rules

    report = report if report is not None else Report()
    if n_shard <= 1:
        return report
    return run_replicated_operand_rules(
        params, {"model": int(n_shard)}, split_axes=("model",),
        rule_id="serving-unsharded-matmul", report=report)


def run_jaxpr_rules(closed, report: Optional[Report] = None) -> Report:
    """All equation-level rules over one traced ClosedJaxpr (the step,
    or any fn traced via :func:`bigdl_tpu.analysis.lint_fn`)."""
    report = report if report is not None else Report()
    levels = list(iter_levels(closed))
    _rule_donation(closed, report)
    _rule_dtype_upcast(levels, report)
    _rule_weak_scalar(levels, report)
    _rule_pallas(levels, report)
    _rule_host_sync(levels, report)
    _rule_collectives(levels, report)
    _rule_quant_dequant_upcast(levels, report)
    return report


# ============================================================ comm rules
def run_comm_rules(params, strategy: Optional[str],
                   grad_compress: Optional[str] = None,
                   report: Optional[Report] = None) -> Report:
    """Gradient-communication rules over one run CONFIGURATION (ISSUE
    10): jit-SPMD traces carry no collective eqns — the partitioner
    inserts the grad all-reduce after lint runs — so what f32 bytes
    would cross the wire is derived from the param tree + strategy +
    --gradCompress instead of from the jaxpr. ``params`` may be real or
    abstract (jax.eval_shape) leaves."""
    report = report if report is not None else Report()
    if strategy not in ("dp", "tp", "sp"):
        return report  # pp/ep own their comm structure; single-device
        # runs have no grad wire
    compress = grad_compress or "off"
    from bigdl_tpu.parallel.grad_comm import (DEFAULT_BUCKET_BYTES,
                                              build_bucket_plan)
    plan = build_bucket_plan(params, DEFAULT_BUCKET_BYTES)
    if compress == "off":
        big = [b for b in plan.buckets if b.nbytes >= COMM_F32_MIN_BYTES]
        if big:
            total = sum(b.nbytes for b in plan.buckets)
            report.add(_finding(
                "comm-f32-allreduce",
                f"--strategy {strategy} all-reduces "
                f"{total / 2**20:.1f} MiB of gradient in f32 "
                f"({len(big)} bucket(s) >= "
                f"{COMM_F32_MIN_BYTES / 2**20:.0f} MiB) with "
                "compression off",
                where=f"grad tree: {plan.n_leaves} leaves, "
                      f"{len(plan.buckets)} bucket(s)",
                hint="--gradCompress bf16 halves the wire bytes "
                     "(bf16+ec keeps optimizer math exactly f32)",
                detail={"bytes_f32": total,
                        "big_buckets": len(big),
                        "n_leaves": plan.n_leaves}))
        n_inexact = plan.n_leaves - len(plan.passthrough)
        if n_inexact > COMM_MAX_COLLECTIVES:
            report.add(_finding(
                "comm-unbucketed",
                f"{n_inexact} gradient leaves reduce without bucketing "
                f"(threshold {COMM_MAX_COLLECTIVES}) — per-leaf "
                "collectives pay launch latency per parameter",
                where=f"grad tree: {plan.n_leaves} leaves",
                hint="--gradCompress bf16 packs them into "
                     f"{len(plan.buckets)} dense bucket(s)",
                detail={"n_leaves": n_inexact,
                        "n_buckets": len(plan.buckets)}))
    return report


# ========================================================= memory rules
def run_memory_rules(plan: Optional[dict],
                     report: Optional[Report] = None) -> Report:
    """HBM working-set rules over one memory plan (ISSUE 12): ``plan``
    is an :func:`bigdl_tpu.obs.memory.build_plan` dict — built from
    abstract pytrees + ``compiled.memory_analysis()``, so it is exact on
    CPU before a chip is touched. Fires **error** when the plan's total
    exceeds the device HBM (the run would RESOURCE_EXHAUST on first
    dispatch) and **warning** above ``HBM_WARN_FRAC`` of capacity.
    ``plan=None`` (plan construction failed) adds nothing."""
    report = report if report is not None else Report()
    if not plan:
        return report
    total = int(plan.get("total_bytes") or 0)
    hbm = int(plan.get("hbm_bytes") or 0)
    if not total or not hbm:
        return report
    frac = total / hbm
    cats = plan.get("categories") or {}
    top = sorted(cats.items(), key=lambda kv: -kv[1])[:3]
    top_s = ", ".join(f"{k} {v / 2**20:.0f} MiB" for k, v in top)
    where = (f"{plan.get('model') or 'step'} b={plan.get('batch')} on "
             f"{plan.get('device') or 'device'}")
    if frac > 1.0:
        report.add(_finding(
            "hbm-oversubscribed",
            f"step working set {total / 2**30:.2f} GiB exceeds device "
            f"HBM {hbm / 2**30:.1f} GiB ({frac * 100:.0f}%) — top: "
            f"{top_s}",
            where=where,
            hint="shrink the batch (bigdl-tpu explain <model> predicts "
                 "the max that fits), drop --optim momentum state, or "
                 "shard the model (--strategy tp)",
            detail={"total_bytes": total, "hbm_bytes": hbm,
                    "frac": round(frac, 4),
                    "categories": dict(cats)}))
    elif frac > HBM_WARN_FRAC:
        report.add(_finding(
            "hbm-tight",
            f"step working set {total / 2**30:.2f} GiB is "
            f"{frac * 100:.0f}% of device HBM {hbm / 2**30:.1f} GiB "
            f"(threshold {HBM_WARN_FRAC * 100:.0f}%) — top: {top_s}",
            where=where,
            hint="headroom this thin ooms on fragmentation; "
                 "bigdl-tpu explain <model> forecasts the fit per batch",
            detail={"total_bytes": total, "hbm_bytes": hbm,
                    "frac": round(frac, 4)}))
    return report


# ========================================================== module rules
def _mod_label(m) -> str:
    n = getattr(m, "name", None)
    cls = type(m).__name__
    return f"{cls}({n})" if n and n != cls else cls


def _ceil128(n: int) -> int:
    return -(-int(n) // 128) * 128


def _rule_bn(model, report: Report) -> None:
    from bigdl_tpu.nn.norm import BatchNormalization

    unfused, ineligible = [], []
    for m in model.modules():
        if not isinstance(m, BatchNormalization):
            continue
        c = int(m.n_output)
        kernel_ok = (m.affine and m.axis_name is None
                     and not m.stat_sample and c % 128 == 0)
        if not kernel_ok:
            if c % 128:
                ineligible.append((f"{_mod_label(m)} C={c}", c))
            continue
        if m.fused != "apply":
            mode = m.fused or "off"
            unfused.append((f"{_mod_label(m)} C={c} fused={mode}", c))
    if unfused:
        report.add(_finding(
            "fusion-bn-unfused",
            f"{len(unfused)} BatchNormalization site(s) eligible for "
            "the fused apply block are running "
            f"{'/'.join(sorted({s.rsplit('=', 1)[-1] for s, _ in unfused}))}"
            " — the config PERF.md §10 measured as the regression",
            where="; ".join(s for s, _ in unfused[:4])
                  + ("…" if len(unfused) > 4 else ""),
            hint="--fusedBN apply (CLI) / set_bn_fused(model, 'apply')",
            detail={"count": len(unfused),
                    "channels": sorted({c for _, c in unfused})}))
    if ineligible:
        report.add(_finding(
            "tile-bn-ineligible",
            f"{len(ineligible)} BN site(s) with C % 128 != 0 "
            f"(C in {sorted({c for _, c in ineligible})}) cannot take "
            "the single-read kernel",
            where="; ".join(s for s, _ in ineligible[:4])
                  + ("…" if len(ineligible) > 4 else ""),
            hint="widen the channel plan to the 128-lane grid where the "
                 "architecture allows",
            detail={"count": len(ineligible)}))


def _conv_geom_args(m) -> tuple:
    """(kh, kw, stride, padding, dilation, groups, cin, cout) of one
    SpatialConvolution-family module."""
    dil = (int(getattr(m, "dilation_h", 1)), int(getattr(m, "dilation_w", 1)))
    return (int(m.kernel_h), int(m.kernel_w),
            (int(m.stride_h), int(m.stride_w)),
            ((int(m.pad_h), int(m.pad_h)), (int(m.pad_w), int(m.pad_w))),
            dil, int(m.n_group),
            int(m.n_input_plane), int(m.n_output_plane))


def _rule_conv_gemm(model, report: Report, dtype="bfloat16") -> None:
    from bigdl_tpu.nn.conv import SpatialConvolution
    from bigdl_tpu.ops.conv2d import gemm_eligible, resolve_site_layouts

    hits = []
    for m in model.modules():
        if not isinstance(m, SpatialConvolution):
            continue
        kh, kw, stride, pad, dil, groups, cin, cout = _conv_geom_args(m)
        if not gemm_eligible(kh, kw, stride, pad, dil, groups):
            continue
        lays = resolve_site_layouts(kh, kw, stride, pad, dil, groups,
                                    cin, cout, dtype)
        spatial = [p for p, l in lays.items() if l != "GEMM"]
        if spatial:
            hits.append((f"{_mod_label(m)} {cin}->{cout} "
                         f"passes={','.join(spatial)}", cin, cout))
    if hits:
        report.add(_finding(
            "fusion-conv-gemm",
            f"{len(hits)} GEMM-eligible 1x1/s1 conv site(s) resolve to "
            "a spatial layout — the dot_general lowering (~half of "
            "ResNet-50's FLOPs live in these sites) is not engaged",
            where="; ".join(s for s, _, _ in hits[:4])
                  + ("…" if len(hits) > 4 else ""),
            hint="--convLayout with GEMM per pass, a --convGeom decision "
                 "file, or --autotune measure on chip",
            detail={"count": len(hits)}))


def _rule_channels(model, report: Report) -> None:
    from bigdl_tpu.nn.conv import SpatialConvolution
    from bigdl_tpu.nn.linear import Linear
    from bigdl_tpu.utils.flops import conv_unit_flops

    hits = []
    for m in model.modules():
        if isinstance(m, SpatialConvolution):
            kh, kw, _, _, _, groups, cin, cout = _conv_geom_args(m)
        elif isinstance(m, Linear):
            kh = kw = groups = 1
            cin, cout = int(m.in_features), int(m.out_features)
        else:
            continue
        if cin % 128 == 0 and cout % 128 == 0:
            continue
        real = conv_unit_flops(1, 1, 1, cin, cout, kh, kw, groups)
        padded = conv_unit_flops(1, 1, 1, _ceil128(cin), _ceil128(cout),
                                 kh, kw, groups)
        waste = 1.0 - real / padded
        hits.append((waste, f"{_mod_label(m)} {cin}->{cout} "
                            f"(~{waste * 100:.0f}% padded MXU tiles)"))
    if hits:
        hits.sort(reverse=True)
        report.add(_finding(
            "layout-c128",
            f"{len(hits)} layer(s) with feature dims off the 128-lane "
            f"grid; worst: {hits[0][1]}",
            where="; ".join(s for _, s in hits[:4])
                  + ("…" if len(hits) > 4 else ""),
            hint="edge layers (stems, heads) are usually unavoidable; "
                 "interior channel plans should stay on multiples of 128",
            detail={"count": len(hits),
                    "worst_waste": round(hits[0][0], 3)}))


def _rule_attention(model, report: Report, seq: Optional[int],
                    dtype="bfloat16") -> None:
    try:
        from bigdl_tpu.nn.attention import MultiHeadAttention
    except Exception:
        return
    from bigdl_tpu.nn.attention import dot_product_attention
    from bigdl_tpu.ops.attention_kernel import flash_attention

    dense, ragged, clamped, headdims = [], [], [], {}
    for m in model.modules():
        if not isinstance(m, MultiHeadAttention):
            continue
        hd = int(m.head_dim)
        if hd % 128:
            headdims[hd] = headdims.get(hd, 0) + 1
        # the constructor resolves attn_impl into self.attn_fn
        fn = getattr(m, "attn_fn", None)
        if fn is None or fn is dot_product_attention:
            dense.append(_mod_label(m))
            continue
        if fn is not flash_attention or not seq:
            continue  # custom/blockwise impls: the user chose them
        from bigdl_tpu.ops.attention_kernel import flash_block_plan
        plan = flash_block_plan(seq, seq, hd, bool(m.causal), dtype)
        if not plan["kernel_ok"]:
            ragged.append((_mod_label(m), plan))
        elif plan["q_pad"] or plan["k_pad"]:
            waste = plan["q_pad"] / (seq + plan["q_pad"])
            report.add(_finding(
                "tile-pad",
                f"{_mod_label(m)}: flash q/k blocks "
                f"({plan['block_q']},{plan['block_k']}) pad seq {seq} "
                f"(~{waste * 100:.0f}% wasted rows)",
                where=_mod_label(m),
                hint="pick a seq the blocks divide, or explicit "
                     "block_q/block_k that divide it"))
        elif plan["clamped"]:
            clamped.append((_mod_label(m), plan))
    if dense:
        report.add(_finding(
            "fusion-attn-dense",
            f"{len(dense)} attention site(s) on the dense XLA path",
            where="; ".join(dense[:4]) + ("…" if len(dense) > 4 else ""),
            hint="attn_impl='flash' (the perf zoo enables it on TPU)",
            detail={"count": len(dense)}))
    if clamped:
        label, plan = clamped[0]
        report.add(_finding(
            "tile-seq-clamp",
            f"{len(clamped)} attention site(s): seq {seq} clamps flash "
            f"blocks to ({plan['block_q']},{plan['block_k']}) — fine, "
            "but a 512-divisible seq keeps the measured-best tiling",
            where=label,
            detail={"count": len(clamped), "block_q": plan["block_q"],
                    "block_k": plan["block_k"]}))
    if ragged:
        label, plan = ragged[0]
        report.add(_finding(
            "tile-ragged-attn",
            f"{len(ragged)} attention site(s): seq {seq} does not tile "
            f"(block_k={plan['block_k']}) — the flash kernel silently "
            "falls back to the remat-scan path",
            where="; ".join(l for l, _ in ragged[:4])
                  + ("…" if len(ragged) > 4 else ""),
            hint="pad/pack sequences to a multiple of 128 "
                 "(dataset.text.pack_sequences) or accept the fallback",
            detail={"seq": seq, "count": len(ragged),
                    **{k: plan[k] for k in ("block_q", "block_k")}}))
    if headdims:
        report.add(_finding(
            "layout-headdim",
            "attention head_dim in "
            f"{sorted(headdims)} half-fills the MXU's 128-wide tiles "
            "(hd128 A/B measured +24% tok/s, PERF.md §8.2)",
            where=f"{sum(headdims.values())} attention site(s)",
            hint="same d_model with fewer, 128-wide heads "
                 "(e.g. transformer_lm_1k_hd128)",
            detail={"head_dims": sorted(headdims)}))


def run_module_rules(model, report: Optional[Report] = None, *,
                     seq: Optional[int] = None,
                     dtype="bfloat16") -> Report:
    """All configuration-level rules over one model tree. ``seq`` (the
    traced sequence length, when known) enables the attention block-plan
    checks; ``dtype`` keys the conv-geometry resolution."""
    report = report if report is not None else Report()
    _rule_bn(model, report)
    _rule_conv_gemm(model, report, dtype=dtype)
    _rule_channels(model, report)
    _rule_attention(model, report, seq, dtype=dtype)
    return report


# shardlint (ISSUE 19) shares this catalog: merge its rule family in so
# the CLI's rule listing and report grouping see one registry
from bigdl_tpu.analysis.sharding_rules import \
    SHARD_CATALOG as _SHARD_CATALOG  # noqa: E402

CATALOG.update(_SHARD_CATALOG)
