"""HBM attribution: explain every byte, forecast the fit, autopsy the
OOM (ISSUE 12 tentpole).

Four surfaces:

* **static plan** — :func:`build_plan` at trace time: per-category byte
  accounting (params / optimizer state / gradients+grad-comm buckets /
  activations+temps / KV cache / input batch) from the abstract pytrees
  plus ``compiled.memory_analysis()`` of the exact step. The category
  table totals to the compiler's number BY CONSTRUCTION: the argument
  bytes are split between the known argument pytrees and an explicit
  ``unattributed`` row, the temp bytes between the gradient estimate and
  ``activations``, so the cross-check can only drift where the abstract
  estimate and the compiler genuinely disagree (and then the drift is a
  visible row, not a silent mismatch).
* **live sampling** — :class:`HbmSampler` wraps ``device.memory_stats()``
  (None on CPU backends — the sampler degrades to a no-op) and publishes
  ``hbm_bytes_in_use`` / ``hbm_peak_bytes`` / ``hbm_largest_free_block``
  gauges on the shared registry plus Chrome-trace counter events so
  Perfetto plots HBM over the same timeline the step phases live on.
* **OOM post-mortem** — the Optimizer dispatch loop and the serving
  engines call :func:`handle_oom` from their RESOURCE_EXHAUSTED catch;
  it writes a MemoryReport (last plan, live stats, top live buffers,
  headroom history) to the installed ``--traceDir`` and stamps the fault
  log like other resilience events, then the caller re-raises.
* **fit forecaster** — :func:`forecast` fits total bytes linearly over
  two plans at different batch sizes (fixed + per-sample slope) and
  predicts the max batch that still fits the device; ``bigdl-tpu
  explain <model>`` renders it (:func:`plan_for_model` /
  :func:`render`).

Like ``resilience.faults``, the cross-layer channel is one module-level
install: ``install(trace_dir=..., plan=..., sampler=...)`` arms the OOM
path process-wide; call sites stay one ``handle_oom(e, ctx)`` line that
can never change the semantics of the run it observes.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Optional, Tuple

logger = logging.getLogger("bigdl_tpu")

__all__ = [
    "HBM_BYTES", "device_hbm_bytes", "tree_bytes", "build_plan",
    "forecast", "plan_for_model", "render", "compact",
    "serving_kv_plan", "forecast_slots",
    "HbmSampler", "install", "installed_plan", "installed_trace_dir",
    "is_resource_exhausted", "handle_oom", "write_oom_report",
    "OOM_REPORT_NAME",
]

# Per-chip HBM capacity (public figures), matched like perf._PEAK_FLOPS:
# substring against the squashed device_kind, most specific first, match
# label reported alongside the number. An accelerator missing from the
# table is an error; the CPU nominal keeps the static plan's headroom
# DEFINED on the CPU test platform (a byte count, not a device metric).
HBM_BYTES = (
    ("v6lite", 32e9), ("v6e", 32e9), ("trillium", 32e9),
    ("v5lite", 16e9), ("v5e", 16e9),
    ("v5p", 95e9),
    ("v4lite", 16e9), ("v4", 32e9),
    ("v3", 16e9), ("v2", 8e9),
    ("cpu", 8e9),  # nominal, so headroom stays defined in CPU test runs
)

OOM_REPORT_NAME = "memory_report.json"

# what build_plan reads off CompiledMemoryStats (jaxlib names)
_MA_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")


def device_hbm_bytes(device=None) -> Tuple[float, str]:
    """Return ``(hbm_bytes, matched_label)`` for one chip (default: the
    first visible device). A kind missing from :data:`HBM_BYTES` raises."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind
    squashed = kind.replace(" ", "").replace("-", "").lower()
    for k, v in HBM_BYTES:
        if k in squashed:
            return v, k
    raise ValueError(
        f"device_kind {kind!r} is not in the HBM capacity table "
        f"(obs/memory.py HBM_BYTES) — add its published capacity rather "
        f"than plan headroom against a made-up one")


def tree_bytes(tree) -> int:
    """Total leaf bytes of a pytree — works on concrete arrays,
    ShapeDtypeStructs, and anything else exposing shape+dtype."""
    if tree is None:
        return 0
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
            continue
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return total


def _grad_comm_pad(grad_comm: Optional[dict]) -> int:
    """Extra bytes the bucketed grad all-reduce holds beyond the raw
    gradient tree (bucket padding + the flat staging buffer is already
    the gradient itself, so only padding counts)."""
    if not grad_comm:
        return 0
    pad = grad_comm.get("pad_bytes")
    if pad is not None:
        return int(pad)
    n = int(grad_comm.get("n_buckets") or 0)
    bb = grad_comm.get("bucket_bytes")
    total = grad_comm.get("total_bytes") or grad_comm.get("wire_bytes")
    if n and bb and total:  # worst-case: last bucket padded to the bound
        return max(0, int(n) * int(bb) - int(total))
    return 0


def build_plan(compiled=None, *, params=None, opt_state=None,
               batch=None, kv_cache=None, grad_comm: Optional[dict] = None,
               device=None, batch_size: Optional[int] = None,
               model_name: Optional[str] = None) -> dict:
    """The static memory plan: a per-category byte table that totals to
    the compiler's number.

    ``compiled`` is the exact lowered+compiled step (or any object with
    ``memory_analysis()``); without it the plan is abstract-only (the
    pre-compile lint path): argument-side categories from the pytrees, a
    params-sized gradient estimate, no activation row.
    """
    params_b = tree_bytes(params)
    opt_b = tree_bytes(opt_state)
    input_b = tree_bytes(batch)
    kv_b = tree_bytes(kv_cache)
    grads_b = params_b + _grad_comm_pad(grad_comm)

    cats = {"params": params_b, "optimizer": opt_b, "gradients": grads_b,
            "activations": 0, "kv_cache": kv_b, "input": input_b,
            "outputs": 0, "unattributed": 0}
    compiler: Optional[dict] = None
    compiler_total: Optional[int] = None
    if compiled is not None:
        ma = compiled.memory_analysis()
        compiler = {f: int(getattr(ma, f, 0) or 0) for f in _MA_FIELDS}
        arg = compiler["argument_size_in_bytes"]
        out = compiler["output_size_in_bytes"]
        tmp = compiler["temp_size_in_bytes"]
        alias = compiler["alias_size_in_bytes"]
        gen = compiler["generated_code_size_in_bytes"]
        compiler_total = arg + tmp + max(0, out - alias) + gen
        # split the argument bytes: known pytrees + explicit remainder.
        # If the abstract sum overshoots (a cast the compiler folded
        # away), scale the known rows down so the table still totals.
        known = params_b + opt_b + input_b + kv_b
        if known <= arg:
            cats["unattributed"] = arg - known
        elif known:
            scale = arg / known
            for k in ("params", "optimizer", "kv_cache", "input"):
                cats[k] = int(cats[k] * scale)
            cats["unattributed"] = arg - (cats["params"] + cats["optimizer"]
                                          + cats["kv_cache"] + cats["input"])
        # split the temp bytes: gradients live inside XLA's temps; what
        # is left over is activations + scratch. A temp smaller than the
        # gradient estimate means the compiler fused gradients away —
        # report what it kept, not the estimate.
        cats["gradients"] = min(grads_b, tmp)
        cats["activations"] = tmp - cats["gradients"]
        # non-aliased outputs: with donation the new params/opt state
        # alias the old ones (alias ~ output); without (CPU) the step
        # genuinely holds both at peak
        cats["outputs"] = max(0, out - alias)
        cats["unattributed"] += gen
        total = sum(cats.values())
    else:
        total = params_b + opt_b + grads_b + input_b + kv_b

    hbm, hbm_label = device_hbm_bytes(device)
    plan = {
        "categories": cats,
        "total_bytes": int(total),
        "compiler": compiler,
        "compiler_total_bytes": compiler_total,
        "hbm_bytes": int(hbm),
        "hbm_match": hbm_label,
        "headroom_bytes": int(hbm - total),
        "headroom_frac": round((hbm - total) / hbm, 4) if hbm else None,
        "batch": batch_size,
    }
    if model_name:
        plan["model"] = model_name
    try:
        import jax
        plan["device"] = getattr(jax.devices()[0], "device_kind", "unknown")
    except Exception:
        plan["device"] = "unknown"
    return plan


def forecast(plan_small: dict, plan_big: dict) -> dict:
    """Linear fit of total bytes over batch size from two plans:
    ``total(b) = fixed + slope * b`` — the slope is the per-sample
    activation+input cost, the intercept the model-resident state.
    Predicts the max batch that still fits the device HBM."""
    na, nb = plan_small.get("batch"), plan_big.get("batch")
    if not na or not nb or na == nb:
        raise ValueError("forecast needs two plans at distinct batch "
                         f"sizes, got {na!r} and {nb!r}")
    if na > nb:
        plan_small, plan_big, na, nb = plan_big, plan_small, nb, na
    ta = float(plan_small["total_bytes"])
    tb = float(plan_big["total_bytes"])
    slope = (tb - ta) / (nb - na)
    fixed = ta - slope * na
    cap = float(plan_big["hbm_bytes"])
    if slope > 0:
        max_batch = int(math.floor((cap - fixed) / slope))
    else:  # degenerate (constant-folded batch, or toy model): no bound
        max_batch = None
    return {
        "bytes_per_sample": int(slope),
        "fixed_bytes": int(fixed),
        "fit_batches": [na, nb],
        "hbm_bytes": int(cap),
        "predicted_max_batch": (max_batch if max_batch is None
                                else max(max_batch, 0)),
    }


def plan_for_model(model_name: str, batch: int,
                   seq_len: Optional[int] = None,
                   use_bf16: bool = False) -> dict:
    """Build, lower, and compile the single-device training step for a
    perf-zoo model at ``batch`` and return its memory plan — the
    ``bigdl-tpu explain`` / forecaster entry point. Mirrors the perf
    harness's step (SGD+momentum, value_and_grad, donated state) so the
    plan describes the bytes a real run would hold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.cli.perf import _LM_VOCAB, build_model
    from bigdl_tpu.optim import SGD

    model, in_shape = build_model(model_name, seq_len=seq_len)
    is_lm = model_name.startswith("transformer_lm")
    crit = (nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
            if is_lm else nn.ClassNLLCriterion())
    opt = SGD(learning_rate=0.01, momentum=0.9)
    dtype = (jnp.bfloat16 if (use_bf16 and jax.default_backend() == "tpu")
             else jnp.float32)

    rng = np.random.RandomState(0)
    if is_lm:
        x = jnp.asarray(rng.randint(0, _LM_VOCAB, (batch, *in_shape))
                        .astype(np.int32))
        y = jnp.asarray(rng.randint(0, _LM_VOCAB, (batch, *in_shape))
                        .astype(np.int32))
    else:
        x = jnp.asarray(np.ones((batch, *in_shape), np.float32))
        y = jnp.asarray(rng.randint(0, 1000 if in_shape[0] > 30 else 10,
                                    batch).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0))
    mod_state = model.init_state()
    opt_state = opt.init(params)

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

    k = jax.random.PRNGKey(1)
    compiled = jax.jit(train_step, donate_argnums=(0, 1, 2)).lower(
        params, mod_state, opt_state, x, y, k).compile()
    return build_plan(compiled, params=params, opt_state=opt_state,
                      batch=(x, y), device=jax.devices()[0],
                      batch_size=batch, model_name=model_name)


def serving_kv_plan(model_name: str, *, seq_len: Optional[int] = None,
                    page_tokens: Optional[int] = None,
                    quantize: Optional[str] = None,
                    cache_dtype=None, device=None) -> dict:
    """Per-slot serving byte accounting for a transformer_lm target: the
    KV-cache cost of one decode slot (dense slab, or the kv8 page-pool
    layout — int8 rows + one f32 scale per (page, head, token), exactly
    :class:`~bigdl_tpu.serving.kv_pages.QuantPool`'s arrays) plus the
    resident weight bytes under ``--quantize``. This is the dtype-aware
    half of ``bigdl-tpu explain``: quantized modes change per-slot and
    fixed bytes, and :func:`forecast_slots` re-fits the max-slot
    prediction from them."""
    import jax
    import numpy as np

    from bigdl_tpu.cli.perf import build_model
    from bigdl_tpu.serving.quant import parse_quantize, quantize_params

    if not model_name.startswith("transformer_lm"):
        raise ValueError("serving_kv_plan targets transformer_lm* models "
                         f"(decode KV slots), got {model_name!r}")
    model, _ = build_model(model_name, seq_len=seq_len)
    wfmt, kv8 = parse_quantize(quantize) if quantize else (None, False)
    L = int(model.max_len)
    pt = page_tokens
    if kv8 and pt is None:
        # same auto ladder the serve CLI uses for --quantize kv8
        for cand in (128, 64, 32, 256):
            if L % cand == 0:
                pt = cand
                break
        if pt is None:
            raise ValueError(f"no page size in (128, 64, 32, 256) "
                             f"divides max_len {L}; pass page_tokens")
    dt = np.dtype(cache_dtype) if cache_dtype is not None \
        else np.dtype(np.float32)
    cache = model.encoder.init_cache(1, L, dt)
    kv_slot = 0
    for leaf in jax.tree_util.tree_leaves(cache):
        _, kh, _, hd = leaf.shape
        if kv8:
            # QuantPool layout: q int8 (pages, kh, pt, hd) + s f32
            # (pages, kh, pt); a slot owns L/pt pages
            kv_slot += (L // pt) * (kh * pt * hd * 1 + kh * pt * 4)
        else:
            kv_slot += int(np.prod(leaf.shape)) * dt.itemsize
    params = model.init(jax.random.PRNGKey(0))
    dense_b = tree_bytes(params)
    if wfmt is not None:
        params = quantize_params(params, wfmt)
    params_b = tree_bytes(params)
    hbm, hbm_label = device_hbm_bytes(device)
    return {
        "model": model_name,
        "quantize": quantize or "off",
        "max_len": L,
        "page_tokens": pt,
        "cache_dtype": dt.name,
        "kv_bytes_per_slot": int(kv_slot),
        "params_bytes": int(params_b),
        "params_bytes_f32": int(dense_b),
        "hbm_bytes": int(hbm),
        "hbm_match": hbm_label,
    }


def forecast_slots(plan: dict, hbm_bytes=None) -> dict:
    """Max decode slots that fit the budget: ``(hbm - weights) /
    kv_bytes_per_slot`` — the serving twin of :func:`forecast`. Under
    kv8 the per-slot cost roughly quarters, so the prediction roughly
    doubles even after the weight savings are counted."""
    cap = float(hbm_bytes if hbm_bytes is not None
                else plan["hbm_bytes"])
    fixed = float(plan["params_bytes"])
    per = float(plan["kv_bytes_per_slot"])
    n = int(math.floor((cap - fixed) / per)) if per > 0 else None
    return {
        "hbm_bytes": int(cap),
        "fixed_bytes": int(fixed),
        "kv_bytes_per_slot": int(per),
        "predicted_max_slots": (max(n, 0) if n is not None else None),
    }


# ------------------------------------------------------------ rendering
def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return (f"{n:.0f} {unit}" if unit == "B"
                    else f"{n:.2f} {unit}")
        n /= 1024.0
    return f"{n:.2f} GiB"


def render(plan: dict, fc: Optional[dict] = None) -> str:
    """Human table of the plan (and forecast, when given)."""
    from bigdl_tpu.utils.table import format_table

    total = max(1, plan["total_bytes"])
    rows = []
    for cat, b in plan["categories"].items():
        if not b:
            continue
        rows.append([cat, _fmt_bytes(b), f"{100.0 * b / total:.1f}%"])
    rows.append(["TOTAL", _fmt_bytes(plan["total_bytes"]), "100.0%"])
    lines = [format_table(["category", "bytes", "frac"], rows)]
    ct = plan.get("compiler_total_bytes")
    if ct is not None:
        drift = (abs(plan["total_bytes"] - ct) / ct * 100.0) if ct else 0.0
        lines.append(f"compiler total      {_fmt_bytes(ct)}  "
                     f"(table drift {drift:.2f}%)")
    lines.append(f"device HBM          {_fmt_bytes(plan['hbm_bytes'])}  "
                 f"(match: {plan['hbm_match']})")
    hf = plan.get("headroom_frac")
    lines.append(f"headroom            "
                 f"{_fmt_bytes(plan['headroom_bytes'])}  "
                 f"({100.0 * hf:.1f}% free)" if hf is not None else
                 f"headroom            {_fmt_bytes(plan['headroom_bytes'])}")
    if fc is not None:
        lines.append("")
        lines.append(f"per-sample slope    "
                     f"{_fmt_bytes(fc['bytes_per_sample'])}/sample "
                     f"(fit over b={fc['fit_batches']})")
        lines.append(f"fixed (model state) {_fmt_bytes(fc['fixed_bytes'])}")
        mb = fc.get("predicted_max_batch")
        lines.append(f"predicted max batch "
                     f"{mb if mb is not None else 'unbounded (flat slope)'}")
    return "\n".join(lines)


def compact(plan: dict) -> dict:
    """The small spelling stamped into perf JSON lines as the ``mem``
    detail dict."""
    return {
        "categories": {k: int(v) for k, v in plan["categories"].items()
                       if v},
        "total_bytes": plan["total_bytes"],
        "compiler_total_bytes": plan.get("compiler_total_bytes"),
        "hbm_bytes": plan["hbm_bytes"],
        "hbm_match": plan["hbm_match"],
        "headroom_frac": plan.get("headroom_frac"),
        "batch": plan.get("batch"),
    }


# --------------------------------------------------------- live sampling
class HbmSampler:
    """Live HBM stats via ``device.memory_stats()``: gauges on the
    shared registry, Chrome-trace counter events, and a bounded headroom
    history for the OOM post-mortem. On backends without memory stats
    (CPU) every sample is a cheap None and the gauges simply never
    appear."""

    def __init__(self, device=None, registry=None, history: int = 512,
                 trace_counters: bool = True):
        if device is None:
            try:
                import jax
                device = jax.devices()[0]
            except Exception:
                device = None
        self.device = device
        self.hbm_bytes, self.hbm_match = device_hbm_bytes(device)
        self.trace_counters = trace_counters
        self.history: list = []  # [(step, bytes_in_use, peak)] bounded
        self._history_cap = int(history)
        self.last: Optional[dict] = None
        self._peak_seen = 0
        self._registered = False
        self._registry = registry

    # stats keys vary slightly across backends; normalize the three the
    # plan/report read
    @staticmethod
    def _normalize(stats: dict) -> dict:
        return {
            "bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)
                                     or 0),
            "largest_free_block_bytes": int(
                stats.get("largest_free_block_bytes", 0) or 0),
        }

    def _ensure_gauges(self) -> None:
        if self._registered:
            return
        try:
            from bigdl_tpu.obs.metrics import get_registry
            reg = self._registry or get_registry()
            reg.gauge("hbm_bytes_in_use", "live device bytes in use",
                      fn=lambda: (self.last or {}).get("bytes_in_use", 0))
            reg.gauge("hbm_peak_bytes", "peak device bytes in use",
                      fn=lambda: self._peak_seen)
            reg.gauge("hbm_largest_free_block_bytes",
                      "largest free block on device",
                      fn=lambda: (self.last or {}).get(
                          "largest_free_block_bytes", 0))
            self._registered = True
        except Exception:  # observability must never kill the run
            pass

    def sample(self, step: Optional[int] = None) -> Optional[dict]:
        """One live reading; returns the normalized stats dict or None
        when the backend has none."""
        if self.device is None:
            return None
        try:
            stats = self.device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            return None
        s = self._normalize(stats)
        self.last = s
        self._peak_seen = max(self._peak_seen,
                              s["peak_bytes_in_use"] or s["bytes_in_use"])
        self._ensure_gauges()
        if len(self.history) >= self._history_cap:
            del self.history[: self._history_cap // 2]
        self.history.append((step, s["bytes_in_use"],
                             s["peak_bytes_in_use"]))
        if self.trace_counters:
            try:
                from bigdl_tpu.obs.spans import counter as _counter
                _counter("hbm", {"bytes_in_use": s["bytes_in_use"],
                                 "largest_free_block":
                                     s["largest_free_block_bytes"]})
            except Exception:
                pass
        return s

    @property
    def peak_bytes(self) -> Optional[int]:
        return self._peak_seen or None

    def annotation(self) -> Optional[dict]:
        if self.last is None:
            return None
        return {"last": dict(self.last), "peak_bytes": self._peak_seen,
                "samples": len(self.history)}


# ------------------------------------------------------ OOM post-mortem
# process-wide context, armed once by install_observability (the same
# one-install channel resilience.faults uses)
_CONTEXT: dict = {"trace_dir": None, "plan": None, "sampler": None}


def install(trace_dir: Optional[str] = None, plan: Optional[dict] = None,
            sampler: Optional[HbmSampler] = None) -> None:
    """Arm the OOM post-mortem path process-wide. Each argument updates
    only when given, so the CLI can install the trace dir early and the
    harness the plan later (post-compile)."""
    if trace_dir is not None:
        _CONTEXT["trace_dir"] = str(trace_dir)
    if plan is not None:
        _CONTEXT["plan"] = plan
    if sampler is not None:
        _CONTEXT["sampler"] = sampler


def installed_plan() -> Optional[dict]:
    return _CONTEXT["plan"]


def installed_trace_dir() -> Optional[str]:
    return _CONTEXT["trace_dir"]


def _reset_context() -> None:  # tests
    _CONTEXT.update(trace_dir=None, plan=None, sampler=None)


def is_resource_exhausted(exc: BaseException) -> bool:
    """Does this exception smell like a device OOM? jax surfaces XLA's
    RESOURCE_EXHAUSTED through XlaRuntimeError (message carries the
    status name); match type name + message so a simulated OOM in tests
    (a RuntimeError with the status string) also qualifies."""
    msg = str(exc)
    return ("RESOURCE_EXHAUSTED" in msg
            or "Resource exhausted" in msg
            or "Out of memory" in msg)


def _top_live_buffers(n: int = 15) -> list:
    """The N largest live device arrays — who is actually holding the
    bytes at crash time."""
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:
        return []
    rows = []
    for a in arrs:
        try:
            rows.append({"shape": list(getattr(a, "shape", ())),
                         "dtype": str(getattr(a, "dtype", "?")),
                         "nbytes": int(getattr(a, "nbytes", 0))})
        except Exception:
            continue
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:n]


def write_oom_report(trace_dir: str, *, context: str,
                     exc: Optional[BaseException] = None,
                     plan: Optional[dict] = None,
                     sampler: Optional[HbmSampler] = None) -> str:
    """Write the MemoryReport JSON to ``trace_dir`` and return its path.
    Pure-function spelling (handle_oom adds the installed-context and
    never-raise wrapping)."""
    report = {
        "event": "oom",
        "context": context,
        "time": time.time(),
        "error": (f"{type(exc).__name__}: {exc}"[:500]
                  if exc is not None else None),
        "plan": plan,
        "live": sampler.annotation() if sampler is not None else None,
        "headroom_history": (list(sampler.history[-64:])
                             if sampler is not None else []),
        "top_live_buffers": _top_live_buffers(),
    }
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, OOM_REPORT_NAME)
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def handle_oom(exc: BaseException, context: str) -> Optional[str]:
    """Call from any RESOURCE_EXHAUSTED catch site (then re-raise).
    Writes the MemoryReport to the installed trace dir, appends the
    event to the fault log (BIGDL_FAULT_LOG, the resilience audit
    trail), drops an instant event on the span timeline, and bumps a
    registry counter. Returns the report path (or None); NEVER raises —
    the autopsy must not change how the crash propagates."""
    try:
        if not is_resource_exhausted(exc):
            return None
        path = None
        trace_dir = _CONTEXT["trace_dir"]
        if trace_dir:
            try:
                path = write_oom_report(trace_dir, context=context,
                                        exc=exc, plan=_CONTEXT["plan"],
                                        sampler=_CONTEXT["sampler"])
                logger.error("OOM in %s: memory report -> %s",
                             context, path)
            except Exception as we:
                logger.warning("OOM report write failed: %s", we)
        else:
            logger.error("OOM in %s (no --traceDir: post-mortem report "
                         "skipped): %s", context, str(exc)[:200])
        # fault-log stamp, the same JSONL + fsync contract as
        # resilience.faults._record (audit survives the crash)
        log_path = os.environ.get("BIGDL_FAULT_LOG")
        if log_path:
            try:
                with open(log_path, "a") as f:
                    f.write(json.dumps({
                        "event": "oom", "context": context,
                        "report": path,
                        "error": f"{type(exc).__name__}: {exc}"[:200],
                        "time": time.time()}) + "\n")
                    f.flush()
                    os.fsync(f.fileno())
            except OSError:
                pass
        try:
            from bigdl_tpu.obs.spans import instant as _instant
            _instant("oom", context=context, report=path)
        except Exception:
            pass
        try:
            from bigdl_tpu.obs.metrics import get_registry
            get_registry().counter(
                "oom_total", "RESOURCE_EXHAUSTED crashes autopsied").inc()
        except Exception:
            pass
        return path
    except Exception as e:  # belt and braces: the autopsy never raises
        logger.warning("OOM handler failed: %s", e)
        return None
