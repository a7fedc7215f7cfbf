"""Per-pass, per-geometry conv layout policy — the consumer of
conv_bwd_probe results.

Why: the round-3 xplane profile (PERF.md §2) put the ResNet-50 backward at
~38% MFU vs the forward's 46%, and ``scripts/conv_bwd_probe.py`` measures
each conv pass (forward, input-grad, filter-grad) under both NHWC and NCHW
activation layouts to find out where the points go. This module is the
part that was missing in round 4 (VERDICT r4 weak #4): a way for a probe
*decision* to change what ``nn.SpatialConvolution`` actually compiles.

Mechanism: :func:`conv2d` is a ``jax.custom_vjp`` whose three passes each
run under an independently chosen activation layout. A non-NHWC pass is
expressed as transpose-in → conv in that layout → transpose-out; XLA fuses
the transposes into neighbors, so the net effect is steering XLA's layout
assignment per pass — exactly what the probe measures, so a probe win
transfers. The backward passes are derived with ``jax.linear_transpose``
of the pass-local conv (no primal recompute; the conv is linear in each
argument), which yields the same transposed-conv HLO autodiff would, but
under the chosen dimension numbers.

Round 8 (ISSUE 3) adds two resolutions the single global triple threw
away:

* **per-geometry decisions** — CONV_PROBE_r05.jsonl records per-shape
  layout asymmetry up to 7x (the stem's wgrad: 0.146 ms NHWC vs 0.021 ms
  NCHW) while the 3x3 stages mildly prefer NHWC; one process-global
  triple can only take the aggregate. Decisions are now keyed by the conv
  *geometry* ``(kh, kw, stride, cin, cout, groups, dilation, dtype)``
  and resolved per pass: an installed :data:`_GEOM_POLICY` entry (probe
  decision via :func:`install_geom_decisions`) wins, then a tuned
  decision from ``bigdl_tpu.tuning`` (``conv_geom`` cache namespace,
  off/cached/measure modes, dry off-TPU), then the global triple.
  An explicit ``--convLayout`` spec still beats everything.
* **a GEMM "layout"** — a 1x1 stride-1 unpadded conv IS a matmul
  (roughly half of ResNet-50's FLOPs), and expressing it as
  ``lax.dot_general`` over ``(N*H*W, Cin) x (Cin, Cout)`` hands XLA the
  mature matmul path instead of the conv lowering. ``GEMM`` is a third
  per-pass choice; each of fwd/dgrad/wgrad independently picks
  NHWC/NCHW/GEMM, and an ineligible site (k>1, strided, padded, grouped
  or dilated) falls back to NHWC — exact parity, never an error.

The policy is process-global trace-time state (layouts are static shape
decisions, not data), set via :func:`set_conv_pass_layouts` /
:func:`install_geom_decisions` or decided from probe output by
:func:`decide_from_probe` / :func:`decide_geom_from_probe`. Default
(all-NHWC, no geometry entries, tuner off) keeps
``nn.SpatialConvolution`` on its plain single-op path — zero change
unless a decision is installed.

The reference has no analog: its layout is fixed by im2col+gemm
(nn/SpatialConvolution.scala:403-430); layout choice on TPU is the
corresponding lever (and GEMM is im2col's degenerate k=1 case, where
im2col is the identity).
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["conv2d", "set_conv_pass_layouts", "get_conv_pass_layouts",
           "decide_from_probe", "decide_geom_from_probe",
           "resolve_layout_spec",
           "install_layout_spec", "maybe_install_auto",
           "install_geom_decisions", "install_geom_file",
           "clear_geom_policy", "geom_policy_if_any", "gemm_eligible",
           "resolve_site_layouts",
           "policy_snapshot", "restore_policy", "policy_active",
           "MEASURED_DECISIONS"]

_PASSES = ("fwd", "dgrad", "wgrad")
_LAYOUTS = ("NHWC", "NCHW", "GEMM")
_DEFAULT = {"fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NHWC"}
_POLICY: Dict[str, str] = dict(_DEFAULT)
# True once a caller installed a policy explicitly (CLI flag or API call);
# maybe_install_auto() then leaves the policy alone
_EXPLICIT = False

# Per-geometry decisions: geometry tuple (see _geom_of) -> partial
# per-pass layout dict, e.g. {(7, 7, 2, 2, 3, 64, 1, 1, 1, "bfloat16"):
# {"wgrad": "NCHW"}}. Consulted per conv site at trace time, before the
# global triple; installed from probe output (install_geom_decisions) —
# tuner-resolved decisions flow in live via bigdl_tpu.tuning instead.
_GEOM_POLICY: Dict[tuple, Dict[str, str]] = {}

# Probe decisions measured on real hardware, shipped as the framework
# default for matching devices. Provenance: round-5 window-2 on-chip
# probe + same-window end-to-end A/B (PERF.md §8.2, CONV_PROBE_r05.jsonl)
# — on TPU v5 lite the filter-grad pass prefers NCHW (aggregate wgrad
# 0.26 ms NHWC vs 0.15 ms NCHW across the ResNet-50 shape set; the stem's
# wgrad alone is 7x: 0.146 vs 0.021 ms) and the decision measured
# +1.1% end-to-end train throughput on ResNet-50 b128 (2,634.8 ->
# 2,662.7 img/s). Unlisted devices resolve to the all-NHWC default.
MEASURED_DECISIONS: Dict[str, Dict[str, str]] = {
    "TPU v5 lite": {"fwd": "NHWC", "dgrad": "NHWC", "wgrad": "NCHW"},
}


def set_conv_pass_layouts(fwd: str = "NHWC", dgrad: str = "NHWC",
                          wgrad: str = "NHWC") -> Dict[str, str]:
    """Install the per-pass activation layouts (each "NHWC", "NCHW" or
    "GEMM" — GEMM applies only at 1x1/stride-1/unpadded/ungrouped conv
    sites and falls back to NHWC elsewhere). Call before jit-compiling
    the train step; layouts are trace-time constants. Returns the
    installed policy."""
    global _EXPLICIT
    for v in (fwd, dgrad, wgrad):
        if v not in _LAYOUTS:
            raise ValueError(
                f"layout must be one of {_LAYOUTS}, got {v!r}")
    _POLICY.update(fwd=fwd, dgrad=dgrad, wgrad=wgrad)
    _EXPLICIT = True
    return dict(_POLICY)


def reset_conv_pass_layouts() -> Dict[str, str]:
    """Restore the all-NHWC default, clear the explicit flag AND drop
    every per-geometry decision, so a subsequent
    :func:`maybe_install_auto` resolves again (tests; a library user who
    wants plain all-NHWC should instead install it explicitly via
    ``set_conv_pass_layouts()``)."""
    global _EXPLICIT
    _POLICY.update(_DEFAULT)
    _EXPLICIT = False
    _GEOM_POLICY.clear()
    return dict(_POLICY)


def resolve_layout_spec(spec: str, device=None) -> Dict[str, str]:
    """Resolve a ``--convLayout`` value to a per-pass dict (not installed).

    ``"default"`` is all-NHWC; ``"auto"`` looks this device's kind up in
    :data:`MEASURED_DECISIONS` (all-NHWC when absent, so auto is safe on
    any backend); ``"FWD,DGRAD,WGRAD"`` is explicit. Raises ValueError on
    a malformed spec."""
    low = (spec or "auto").strip().lower()
    if low == "default":
        return dict(_DEFAULT)
    if low == "auto":
        if device is None:
            try:
                device = jax.devices()[0]
            except Exception:
                return dict(_DEFAULT)
        return dict(MEASURED_DECISIONS.get(
            getattr(device, "device_kind", ""), _DEFAULT))
    parts = spec.strip().upper().split(",")
    if len(parts) != 3 or any(p not in _LAYOUTS for p in parts):
        raise ValueError("convLayout spec wants FWD,DGRAD,WGRAD "
                         "(NHWC|NCHW|GEMM each), 'auto' or 'default'; "
                         f"got {spec!r}")
    return dict(zip(_PASSES, parts))


def install_layout_spec(spec: str, device=None) -> Dict[str, str]:
    """Resolve ``spec`` and install it as an explicit policy (wins over
    any later :func:`maybe_install_auto`). Returns the installed dict."""
    return set_conv_pass_layouts(**resolve_layout_spec(spec, device))


def conv_layouts_if_nondefault() -> "Dict[str, str] | None":
    """The active policy when it differs from all-NHWC, else None —
    result-JSON provenance helper for the perf/TTA harnesses."""
    return None if _POLICY == _DEFAULT else dict(_POLICY)


def maybe_install_auto(device=None, guarded: bool = False,
                       policy: "Dict[str, str] | None" = None
                       ) -> Dict[str, str]:
    """Install this device's measured decision (or an explicit ``policy``
    dict from the autotuner) unless a policy was already installed
    explicitly. Called by the training entry points (Optimizer, perf
    harness) right before compiling, when the backend is known — this is
    how a shipped probe decision becomes the framework default without
    overriding a user's ``--convLayout``.

    ``guarded=True`` marks a run configuration where the measured
    decision is known-negative (inner-stepping, the s2d stem — PERF.md
    §8.2 combination matrix): the all-NHWC default is INSTALLED, not
    merely skipped, so a K=1 run followed by a K>1 run in one process
    keeps plain-path semantics (ADVICE r5 #1). Returns the active
    policy."""
    if not _EXPLICIT:
        if guarded:
            _POLICY.update(_DEFAULT)
        elif policy is not None:
            for v in policy.values():
                if v not in _LAYOUTS:
                    raise ValueError(
                        f"layout must be one of {_LAYOUTS}, got {v!r}")
            _POLICY.update({p: policy[p] for p in _PASSES})
        else:
            _POLICY.update(resolve_layout_spec("auto", device))
    return dict(_POLICY)


def policy_snapshot() -> tuple:
    """Capture (policy, explicit-flag, per-geometry table) so a harness
    can restore the pre-run state afterwards — the per-run isolation half
    of the ADVICE r5 #1 fix (one process running K=1 then K>1 must not
    leak the measured layout into the guarded run). The geometry table
    rides along so mixed global+per-geometry state round-trips whole."""
    return (dict(_POLICY), _EXPLICIT,
            {g: dict(v) for g, v in _GEOM_POLICY.items()})


def restore_policy(snap: tuple) -> Dict[str, str]:
    """Restore a :func:`policy_snapshot` (pre-round-8 two-tuples restore
    with an empty geometry table)."""
    global _EXPLICIT
    pol, explicit = snap[0], snap[1]
    _POLICY.update({p: pol[p] for p in _PASSES})
    _EXPLICIT = bool(explicit)
    _GEOM_POLICY.clear()
    if len(snap) > 2:
        _GEOM_POLICY.update({g: dict(v) for g, v in snap[2].items()})
    return dict(_POLICY)


def get_conv_pass_layouts() -> Dict[str, str]:
    return dict(_POLICY)


def is_default_policy() -> bool:
    return _POLICY == _DEFAULT


def policy_active() -> bool:
    """True when a conv layout decision of ANY kind can apply: a
    non-default global triple, an installed per-geometry table, or a
    non-off autotune mode (which may hold per-geometry ``conv_geom``
    decisions to consult at trace time). ``nn.SpatialConvolution`` routes
    through :func:`conv2d` exactly when this is true — otherwise it keeps
    its plain single-op path."""
    if _POLICY != _DEFAULT or _GEOM_POLICY:
        return True
    try:
        from bigdl_tpu.tuning.autotune import get_mode
        return get_mode() != "off"
    except Exception:
        return False


def probe_totals(lines: Iterable[str]) -> Dict[str, Dict[str, float]]:
    """Aggregate conv_bwd_probe JSONL rows into per-pass, per-layout total
    milliseconds across all probed shapes (total ms ≈ one ResNet-50-ish
    step's conv time, so the sum is the right weighting). Non-JSON lines
    are skipped. Raises on zero usable rows."""
    totals = {p: {"NHWC": 0.0, "NCHW": 0.0} for p in _PASSES}
    counts = {p: {"NHWC": 0, "NCHW": 0} for p in _PASSES}
    for line in lines:
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        lay = row.get("layout")
        if lay not in ("NHWC", "NCHW"):
            continue
        for p in _PASSES:
            ms = row.get(f"{p}_ms")
            if ms is not None:
                totals[p][lay] += float(ms)
                counts[p][lay] += 1
    if not any(c for per in counts.values() for c in per.values()):
        raise ValueError("no probe rows found")
    for p in _PASSES:
        # a truncated probe (killed mid-run) can leave one layout
        # unmeasured at 0.0 ms — which min() would then always "win";
        # refuse to decide from asymmetric coverage
        if counts[p]["NHWC"] != counts[p]["NCHW"]:
            raise ValueError(
                f"asymmetric probe coverage for pass {p!r}: "
                f"{counts[p]['NHWC']} NHWC vs {counts[p]['NCHW']} NCHW "
                "rows — probe was truncated, re-run it")
    return totals


def decide_from_probe(lines: Iterable[str]) -> Dict[str, str]:
    """Per-pass layout decision from probe rows: the layout with the lower
    :func:`probe_totals` time wins each pass. Returns {'fwd'|'dgrad'|
    'wgrad': layout} without installing it."""
    totals = probe_totals(lines)
    return {p: min(totals[p], key=totals[p].get) for p in _PASSES}


# ------------------------------------------------------ per-geometry policy
def _dtype_name(dtype) -> str:
    """Canonical dtype spelling for geometry keys ("float32",
    "bfloat16") — matches tuning.autotune's spelling so the two key
    spaces can never drift."""
    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def _geom_of(x, w, stride, rhs_dilation, groups) -> tuple:
    """The geometry key of one conv site, from trace-time avals:
    (kh, kw, sh, sw, cin, cout, groups, dh, dw, dtype). Batch and spatial
    extent are deliberately NOT part of the key — the probe showed the
    asymmetry tracks kernel/channel/stride structure, and one decision
    per geometry keeps the table (and the measure cost) bounded."""
    return (int(w.shape[0]), int(w.shape[1]), int(stride[0]),
            int(stride[1]), int(x.shape[-1]), int(w.shape[-1]),
            int(groups), int(rhs_dilation[0]), int(rhs_dilation[1]),
            _dtype_name(x.dtype))


def geom_to_json(g: tuple) -> dict:
    """JSON spelling of a geometry key (stable field order via sort_keys
    at dump time)."""
    return {"kh": g[0], "kw": g[1], "stride": [g[2], g[3]],
            "cin": g[4], "cout": g[5], "groups": g[6],
            "dilation": [g[7], g[8]], "dtype": g[9]}


def geom_from_json(d: dict) -> tuple:
    """Inverse of :func:`geom_to_json`; raises ValueError on a malformed
    geometry dict."""
    try:
        s, dil = d["stride"], d.get("dilation", [1, 1])
        return (int(d["kh"]), int(d["kw"]), int(s[0]), int(s[1]),
                int(d["cin"]), int(d["cout"]), int(d.get("groups", 1)),
                int(dil[0]), int(dil[1]), str(d.get("dtype", "bfloat16")))
    except (KeyError, TypeError, IndexError) as e:
        raise ValueError(f"malformed conv geometry {d!r}: {e}")


def gemm_eligible(kh: int, kw: int, stride, padding, rhs_dilation,
                  groups: int) -> bool:
    """True when the conv site is exactly a matmul: 1x1 kernel, stride 1,
    zero padding, no dilation, no grouping. Everywhere else the GEMM
    choice silently degrades to NHWC (exact-parity fallback)."""
    if kh != 1 or kw != 1 or int(groups) != 1:
        return False
    if tuple(int(s) for s in stride) != (1, 1):
        return False
    if tuple(int(d) for d in rhs_dilation) != (1, 1):
        return False
    if isinstance(padding, str):  # "SAME"/"VALID" spellings: only VALID
        return padding.upper() == "VALID"  # is zero-pad, and 1x1 SAME ==
        # VALID anyway, but don't guess
    return all(int(lo) == 0 and int(hi) == 0 for lo, hi in padding)


def install_geom_decisions(decisions: Iterable[dict]) -> int:
    """Install per-geometry decisions (the JSON
    ``scripts/apply_conv_probe.py --geom`` emits): each item is
    ``{"geom": {...}, "layouts": {"fwd"|"dgrad"|"wgrad": layout}}``.
    Unknown passes/layouts raise — a typo'd decision file must not
    silently train differently. Returns the number of geometry entries
    installed. Explicit ``--convLayout`` still wins at lookup time."""
    n = 0
    for d in decisions:
        g = geom_from_json(d.get("geom", {}))
        lays = d.get("layouts") or {}
        for p, v in lays.items():
            if p not in _PASSES or v not in _LAYOUTS:
                raise ValueError(
                    f"bad per-geometry decision {p!r}={v!r} (passes "
                    f"{_PASSES}, layouts {_LAYOUTS})")
        if lays:
            _GEOM_POLICY.setdefault(g, {}).update(lays)
            n += 1
    return n


def install_geom_file(path: str) -> int:
    """Load a per-geometry decision JSON file (a list, or
    ``{"decisions": [...]}``) and install it — the ``--convGeom FILE``
    CLI spelling."""
    with open(path) as f:
        blob = json.load(f)
    if isinstance(blob, dict):
        blob = blob.get("decisions", [])
    return install_geom_decisions(blob)


def clear_geom_policy() -> None:
    _GEOM_POLICY.clear()


def geom_policy_if_any() -> "List[dict] | None":
    """The installed per-geometry decisions as a deterministic JSON-able
    list, or None when the table is empty — result-JSON provenance
    (every perf line says which per-geometry policy it ran under)."""
    if not _GEOM_POLICY:
        return None
    return [{"geom": geom_to_json(g), "layouts": dict(_GEOM_POLICY[g])}
            for g in sorted(_GEOM_POLICY)]


# conv_bwd_probe.py rows predating round 8 carry only a shape *name*;
# this maps the historical names (CONV_PROBE_r05.jsonl) to geometries so
# old probe archives still yield per-geometry decisions.
LEGACY_PROBE_SHAPES: Dict[str, tuple] = {
    "stem7x7s2": (7, 7, 2, 2, 3, 64, 1, 1, 1, "bfloat16"),
    "s1_3x3": (3, 3, 1, 1, 64, 64, 1, 1, 1, "bfloat16"),
    "s2_3x3": (3, 3, 1, 1, 128, 128, 1, 1, 1, "bfloat16"),
    "s3_3x3": (3, 3, 1, 1, 256, 256, 1, 1, 1, "bfloat16"),
    "s4_3x3": (3, 3, 1, 1, 512, 512, 1, 1, 1, "bfloat16"),
    "s2_1x1": (1, 1, 1, 1, 512, 128, 1, 1, 1, "bfloat16"),
}


def _row_geom(row: dict) -> "tuple | None":
    """Geometry of one probe row: explicit fields when present (round-8
    probe), the legacy name table otherwise."""
    if "kh" in row:
        try:
            return geom_from_json(row)
        except ValueError:
            return None
    return LEGACY_PROBE_SHAPES.get(row.get("shape", ""))


def decide_geom_from_probe(lines: Iterable[str]) -> List[dict]:
    """Per-geometry, per-pass layout decisions from probe rows: for each
    geometry, each pass independently takes the layout with the lowest
    measured time across the layouts probed for that geometry (NHWC/NCHW
    always; GEMM where the probe measured it). Deterministic: geometries
    sorted, ties broken by the fixed layout order NHWC < NCHW < GEMM.
    Returns the decision list without installing it."""
    best: Dict[tuple, Dict[str, Tuple[float, str]]] = {}
    for line in lines:
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        lay = row.get("layout")
        if lay not in _LAYOUTS:
            continue
        g = _row_geom(row)
        if g is None:
            continue
        rank = _LAYOUTS.index(lay)
        per = best.setdefault(g, {})
        for p in _PASSES:
            ms = row.get(f"{p}_ms")
            if ms is None:
                continue
            cand = (float(ms), rank, lay)
            if p not in per or cand < per[p]:
                per[p] = cand
    if not best:
        raise ValueError("no usable probe rows (geometry fields or a "
                         "known legacy shape name required)")
    out = []
    for g in sorted(best):
        out.append({"geom": geom_to_json(g),
                    "layouts": {p: best[g][p][2] for p in _PASSES
                                if p in best[g]}})
    return out


def resolve_site_layouts(kh: int, kw: int, stride, padding, rhs_dilation,
                         groups: int, cin: int, cout: int,
                         dtype="bfloat16") -> Dict[str, str]:
    """What layout each pass of ONE conv site would resolve to under the
    currently-installed policy — the same precedence ladder
    :func:`_pass_layout` applies at trace time (explicit spec >
    per-geometry decision > cached tuner decision > global triple, GEMM
    degrading to NHWC at ineligible sites) but computed from static site
    metadata, with the tuner consulted READ-ONLY (no measuring, no cache
    writes, no ledger entries). This is tpulint's layout/fusion oracle
    (bigdl_tpu.analysis): a GEMM-eligible site resolving to a spatial
    layout is a fusion-opportunity finding."""
    stride = tuple(int(s) for s in stride)
    rhs_dilation = tuple(int(d) for d in rhs_dilation)
    geom = (int(kh), int(kw), stride[0], stride[1], int(cin), int(cout),
            int(groups), rhs_dilation[0], rhs_dilation[1],
            _dtype_name(dtype))
    ok = gemm_eligible(int(kh), int(kw), stride, padding, rhs_dilation,
                       int(groups))
    out: Dict[str, str] = {}
    for p in _PASSES:
        lay = None
        if not _EXPLICIT:
            per = _GEOM_POLICY.get(geom)
            if per:
                lay = per.get(p)
            if lay is None:
                lay = _peek_tuned_geom(p, geom, ok)
        if lay is None:
            lay = _POLICY[p]
        if lay == "GEMM" and not ok:
            lay = "NHWC"
        out[p] = lay
    return out


def _peek_tuned_geom(pass_name: str, geom: tuple,
                     gemm_ok: bool) -> "str | None":
    """Read-only view of the tuner's ``conv_geom`` decision for one
    (pass, geometry) — unlike :func:`_tuned_geom_layout` this can never
    measure, write a dry entry, or touch the provenance ledger."""
    try:
        from bigdl_tpu.tuning import autotune as _at
    except Exception:
        return None
    if _at.get_mode() == "off":
        return None
    return _at.peek_geom_layout(pass_name, geom, gemm_ok)


def _to_nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


def _to_nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def _conv_in_layout(x, w, stride, padding, rhs_dilation, groups, layout):
    """NHWC/HWIO in, NHWC out — internal conv under ``layout``'s dimension
    numbers (the transposes are XLA-fused into neighbors). ``GEMM``
    expresses the (already-validated 1x1/s1/unpadded) conv as a single
    ``dot_general`` over the flattened pixels — the contraction is
    identical (sum over Cin), so FLOPs and math match the conv spelling;
    only the lowering changes (XLA's matmul path instead of conv)."""
    if layout == "GEMM":
        n, h, wd, cin = x.shape
        cout = w.shape[-1]
        y = lax.dot_general(x.reshape(n * h * wd, cin),
                            w.reshape(cin, cout),
                            (((1,), (0,)), ((), ())))
        return y.reshape(n, h, wd, cout)
    if layout == "NHWC":
        return lax.conv_general_dilated(
            x, w, stride, padding, rhs_dilation=rhs_dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
    y = lax.conv_general_dilated(
        _to_nchw(x), jnp.transpose(w, (3, 2, 0, 1)), stride, padding,
        rhs_dilation=rhs_dilation,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)
    return _to_nhwc(y)


def _pass_layout(pass_name, x, w, stride, padding, rhs_dilation, groups):
    """Resolve ONE pass's layout at trace time. Precedence: explicit
    ``--convLayout`` spec > installed per-geometry decision > tuned
    ``conv_geom`` decision (autotune cached/measure) > global triple.
    A GEMM choice at an ineligible site degrades to NHWC — exact-parity
    fallback, never an error (a probe decision file must not be able to
    crash a training run at a geometry it never measured)."""
    lay = None
    if not _EXPLICIT:
        g = _geom_of(x, w, stride, rhs_dilation, groups)
        per = _GEOM_POLICY.get(g)
        if per:
            lay = per.get(pass_name)
        if lay is None:
            lay = _tuned_geom_layout(pass_name, g, x.shape, padding)
    if lay is None:
        lay = _POLICY[pass_name]
    if lay == "GEMM" and not gemm_eligible(
            int(w.shape[0]), int(w.shape[1]), stride, padding,
            rhs_dilation, groups):
        lay = "NHWC"
    return lay


def _tuned_geom_layout(pass_name, geom, x_shape, padding):
    """Per-geometry decision from the autotuner's ``conv_geom`` cache
    namespace (None when the tuner is off / misses — the caller then
    falls back to the global triple). Imported lazily: ops must not pull
    the tuning package in at import time."""
    try:
        from bigdl_tpu.tuning import autotune as _at
    except Exception:
        return None
    if _at.get_mode() == "off":
        return None
    gemm_ok = gemm_eligible(geom[0], geom[1], (geom[2], geom[3]), padding,
                            (geom[7], geom[8]), geom[6])
    return _at.conv_geom_layout(
        pass_name, geom, tuple(int(d) for d in x_shape), gemm_ok)


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def conv2d(x, w, stride: Tuple[int, int], padding, rhs_dilation,
           groups: int):
    """2-D conv, NHWC x / HWIO w, with the per-pass (and per-geometry)
    layout policy applied. stride/padding/rhs_dilation must be hashable
    tuples (static)."""
    return _conv_in_layout(
        x, w, stride, padding, rhs_dilation, groups,
        _pass_layout("fwd", x, w, stride, padding, rhs_dilation, groups))


def _fwd(x, w, stride, padding, rhs_dilation, groups):
    y = _conv_in_layout(
        x, w, stride, padding, rhs_dilation, groups,
        _pass_layout("fwd", x, w, stride, padding, rhs_dilation, groups))
    return y, (x, w)


def _bwd(stride, padding, rhs_dilation, groups, res, dy):
    x, w = res
    dg = _pass_layout("dgrad", x, w, stride, padding, rhs_dilation, groups)
    wg = _pass_layout("wgrad", x, w, stride, padding, rhs_dilation, groups)
    dx, = jax.linear_transpose(
        lambda xx: _conv_in_layout(xx, w, stride, padding, rhs_dilation,
                                   groups, dg), x)(dy)
    dw, = jax.linear_transpose(
        lambda ww: _conv_in_layout(x, ww, stride, padding, rhs_dilation,
                                   groups, wg), w)(dy)
    return dx, dw


conv2d.defvjp(_fwd, _bwd)
