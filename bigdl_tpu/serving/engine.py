"""Online inference engine: bucketed pre-compiled forwards over a
restored eval-mode module.

The training half of the stack compiles ONE step shape and reuses it for
hours; serving sees a new batch geometry on every request. Left to
``jax.jit`` alone that means a fresh XLA compile per distinct request
count — tens of seconds of p99 on a TPU for a shape the compile cache
has never seen. The engine therefore admits only a fixed, declared set
of batch **buckets**: a request of n rows pads up to the smallest bucket
>= n (chunking through the largest bucket first when n exceeds it), so
the compile cache is bounded by ``len(buckets)`` programs per input
geometry and the steady state recompiles nothing. Padding waste is
metered (``padded_rows_total`` vs ``rows_total``) so the bucket ladder
can be re-fit to observed traffic.

The same tuned program the perf harness measured is what serves: the
caller installs ``--fusedBN``/``--convLayout``/``--convGeom``/
``--autotune`` before construction (cli/serve.py mirrors the perf
flags), inputs are donated into the jitted forward, activations
optionally run bf16, and the tpulint pre-flight (`bigdl_tpu.analysis`)
runs over the exact serving graph BEFORE the first compile — strict mode
refuses to serve a graph with error-severity findings.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.obs.spans import span as _obs_span
from bigdl_tpu.resilience.faults import hook as _fault_hook
from bigdl_tpu.serving.reqtrace import get as _get_reqtracer

logger = logging.getLogger(__name__)

__all__ = ["InferenceEngine", "power_of_two_buckets"]


def power_of_two_buckets(max_batch: int, min_bucket: int = 1) -> tuple:
    """The default bucket ladder: powers of two from ``min_bucket`` up to
    and including ``max_batch`` (which is always a member, power of two
    or not) — log2(max_batch) compiles bound the cache, and tail batches
    waste at most half a bucket."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = []
    b = max(1, min_bucket)
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(sorted(set(out)))


class InferenceEngine:
    """Eval-mode forward over fixed batch buckets.

    ``predict_scores(x)`` accepts any row count, pads each chunk to a
    bucket, runs the compiled forward, and strips the padding — output
    is row-for-row what an unpadded forward would produce (padding rows
    never influence real rows: eval-mode modules are row-independent;
    BN runs on frozen stats).

    ``compute_dtype`` (e.g. bf16) casts floating inputs before the
    module — int inputs (LM tokens) pass through and the module's own
    ``compute_dtype`` handles the post-embedding cast.
    """

    def __init__(self, module, params, mod_state=None, *,
                 buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 compute_dtype=None, donate_inputs: bool = True,
                 lint: Optional[str] = None, metrics=None,
                 mesh=None, model_axis: str = "model",
                 quantize: Optional[str] = None):
        import jax

        self.module = module
        # quantized weights (ISSUE 17) go 8-bit BEFORE mesh placement so
        # scales ride their weight's layout; "off"/None never touches
        # the tree (byte-identical serving path, CI-enforced)
        from bigdl_tpu.serving import quant as _q
        self.quantize = quantize if quantize else "off"
        wfmt, _ = _q.parse_quantize(quantize)
        if wfmt is not None:
            params = _q.quantize_params(params, wfmt)
        # tp placement (ISSUE 16): params committed to the mesh under
        # the training-side Megatron layout; GSPMD partitions the
        # bucketed forwards from there. A 1-device mesh just pins the
        # engine to a dp replica's chip; mesh=None is the single-chip
        # path unchanged.
        self.mesh = mesh
        if mesh is not None:
            from bigdl_tpu.serving.sharding import ServingSharding
            self._shard = ServingSharding(mesh, axis=model_axis)
            params = self._shard.place_params(module, params)
            if mod_state is not None:
                mod_state = jax.device_put(mod_state,
                                           self._shard.replicated)
        else:
            self._shard = None
        self.params = params
        self.mod_state = (mod_state if mod_state is not None
                          else module.init_state())
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.compute_dtype = compute_dtype
        self.donate_inputs = donate_inputs
        self.lint_mode = lint if lint in ("on", "strict") else None
        self.lint_annotation = None
        self._linted = False
        self._compiled = {}  # (bucket, feat_shape, dtype_str) -> jitted fn
        self._compile_lock = threading.Lock()
        # ISSUE 12: compile-time memory per bucket (memory_analysis of
        # the exact AOT-compiled program), stamped into provenance so a
        # bucket ladder's HBM cost is visible before traffic arrives
        self._bucket_mem: dict = {}

        if metrics is not None:
            self._m_rows = metrics.counter(
                "rows_total", "input rows submitted to the engine")
            self._m_pad = metrics.counter(
                "padded_rows_total",
                "bucket-padding rows (waste) run alongside real rows")
            self._m_compiles = metrics.counter(
                "compiles_total", "distinct (bucket, geometry) compiles")
            metrics.gauge(
                "padding_waste_fraction",
                "padded_rows_total / (rows_total + padded_rows_total)",
                fn=self._padding_waste)
        else:
            self._m_rows = self._m_pad = self._m_compiles = None

        def fwd(params, mod_state, x):
            import jax.numpy as jnp
            if (self.compute_dtype is not None
                    and jnp.issubdtype(x.dtype, jnp.floating)):
                x = x.astype(self.compute_dtype)
            y, _ = module.apply(params, mod_state, x, training=False)
            return y

        self._fwd = fwd
        self._jax = jax

    # -------------------------------------------------------- construction
    @classmethod
    def from_checkpoint(cls, module, path: str, mesh=None,
                        **kw) -> "InferenceEngine":
        """Restore an inference-only view of a training checkpoint
        (params + mod_state, no optimizer state — single-blob model.<n>
        or sharded orbax; clean SystemExit on missing/corrupt).

        With ``mesh`` (ISSUE 16) the blob loads through PR 10's
        ``restore_resharded`` — checkpoints written under ANY training
        topology place onto ANY serving topology, manifest-validated —
        and the engine re-shards params to the serving tp layout."""
        if mesh is not None:
            from bigdl_tpu.serving.sharding import restore_for_serving
            params, mod_state = restore_for_serving(path, mesh)
            return cls(module, params, mod_state, mesh=mesh, **kw)
        from bigdl_tpu.utils.orbax_ckpt import restore_for_inference
        params, mod_state = restore_for_inference(path)
        return cls(module, params, mod_state, **kw)

    def _padding_waste(self) -> float:
        if self._m_rows is None:
            return 0.0
        real, pad = self._m_rows.value, self._m_pad.value
        total = real + pad
        return (pad / total) if total else 0.0

    # --------------------------------------------------------------- lint
    def preflight_lint(self, feat_shape, dtype) -> int:
        """tpulint over the exact serving forward (largest bucket) before
        anything compiles. Returns the report's exit code (0 = serve;
        nonzero = strict mode found error-severity findings). The
        summary annotation is kept for provenance stamping either way."""
        if self.lint_mode is None or self._linted:
            return 0
        self._linted = True
        import jax

        from bigdl_tpu.analysis import lint_fn
        from bigdl_tpu.cli.common import run_preflight_lint

        x = jax.ShapeDtypeStruct((self.buckets[-1],) + tuple(feat_shape),
                                 dtype)
        jitted = jax.jit(self._fwd)
        report = lint_fn(jitted, self.params, self.mod_state, x)
        rc, ann = run_preflight_lint(report,
                                     strict=(self.lint_mode == "strict"))
        self.lint_annotation = ann if rc == 0 else report.annotation()
        return rc

    # ------------------------------------------------------------- compile
    def _get_compiled(self, bucket: int, feat_shape: tuple, dtype):
        key = (bucket, feat_shape, str(dtype))
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        with self._compile_lock:
            fn = self._compiled.get(key)
            if fn is None:
                if self.lint_mode is not None and not self._linted:
                    rc = self.preflight_lint(feat_shape, dtype)
                    if rc:
                        raise SystemExit(rc)
                # CPU can't donate (XLA copies + warns every compile);
                # the buffer-reuse win only exists on device backends
                donate = ((2,) if self.donate_inputs
                          and self._jax.default_backend() != "cpu" else ())
                # AOT-compile so the program's memory footprint is
                # known NOW and the compiled program is served as-is; a
                # compiler refusal surfaces here, at startup, not on a
                # later request through a second lazy compile
                x_abs = self._jax.ShapeDtypeStruct(
                    (bucket,) + tuple(feat_shape), dtype)
                fn = self._jax.jit(
                    self._fwd, donate_argnums=donate).lower(
                        self.params, self.mod_state, x_abs).compile()
                ma = fn.memory_analysis()
                arg = int(getattr(ma, "argument_size_in_bytes", 0))
                out_b = int(getattr(ma, "output_size_in_bytes", 0))
                tmp = int(getattr(ma, "temp_size_in_bytes", 0))
                alias = int(getattr(ma, "alias_size_in_bytes", 0))
                self._bucket_mem[bucket] = {
                    "argument_bytes": arg, "output_bytes": out_b,
                    "temp_bytes": tmp,
                    "total_bytes": arg + tmp + max(0, out_b - alias)}
                self._compiled[key] = fn
                if self._m_compiles is not None:
                    self._m_compiles.inc()
                logger.info("serving compile: bucket=%d feat=%s dtype=%s "
                            "(%d cached)", bucket, feat_shape, dtype,
                            len(self._compiled))
        return fn

    def warmup(self, feat_shape, dtype=np.float32,
               buckets: Optional[Sequence[int]] = None) -> None:
        """Pre-compile (and execute once, so XLA autotuning settles)
        every bucket at the given input geometry — pays the compile cost
        at startup instead of on the first unlucky request."""
        for b in (buckets or self.buckets):
            x = np.zeros((b,) + tuple(feat_shape), dtype)
            fn = self._get_compiled(b, tuple(feat_shape), np.dtype(dtype))
            np.asarray(fn(self.params, self.mod_state,
                          self._jax.numpy.asarray(x)))

    # ------------------------------------------------------------- predict
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n, or the largest bucket (callers chunk)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def predict_scores(self, x, rids=None) -> np.ndarray:
        """Raw model outputs for every row of ``x`` (any row count).

        ``rids`` (ISSUE 15) is an optional per-row sequence of request
        ids aligned with ``x``: each compiled-chunk forward attributes
        its compute window back to exactly the requests whose rows it
        carried, so a request split across chunks gets the union."""
        # fault-injection site for the serving forward (no-op unless a
        # --faultPlan is installed): a `worker_kill` here is fatal to
        # the batcher worker — the dead-worker/watchdog drill
        _fault_hook("infer")
        x = np.asarray(x)
        n = len(x)
        if n == 0:
            return np.zeros((0,), np.float32)
        rt = _get_reqtracer() if rids is not None else None
        feat_shape = tuple(x.shape[1:])
        dtype = x.dtype
        outs = []
        i = 0
        while i < n:
            take = min(n - i, self.buckets[-1])
            bucket = self.bucket_for(take)
            chunk = x[i:i + take]
            pad = bucket - take
            if pad > 0:
                # repeat the last real row (a benign, in-distribution
                # filler — all-zeros can NaN under log/normalization)
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], pad, axis=0)])
            fn = self._get_compiled(bucket, feat_shape, dtype)
            with _obs_span("infer", bucket=bucket, rows=take):
                t0c = rt.clock() if rt is not None else 0.0
                try:
                    y = fn(self.params, self.mod_state,
                           self._jax.numpy.asarray(chunk))
                except Exception as e:
                    # RESOURCE_EXHAUSTED autopsy (ISSUE 12): report to
                    # --traceDir + fault log, then fail the request
                    # exactly as before
                    from bigdl_tpu.obs import memory as _obs_mem
                    _obs_mem.handle_oom(e, "serving_predict")
                    raise
                outs.append(np.asarray(y)[:take])
                if rt is not None:
                    t1c = rt.clock()
                    for rid in rids[i:i + take]:
                        if rid is not None:
                            rt.note_compute(rid, t0c, t1c)
            if self._m_rows is not None:
                self._m_rows.inc(take)
                self._m_pad.inc(pad)
            i += take
        return np.concatenate(outs)

    def predict(self, x) -> np.ndarray:
        """Argmax class ids (the Classifier-compatible surface)."""
        scores = self.predict_scores(x)
        if len(scores) == 0:
            return np.zeros((0,), np.int64)
        return np.argmax(scores, axis=-1)

    # ---------------------------------------------------------- provenance
    def provenance(self) -> dict:
        """Serving config provenance for /metrics scrapes and bench JSON
        lines — the same fields the perf harness stamps (bn_fused, conv
        layout source, autotune mode, lint summary) plus the bucket set,
        so every latency number is attributable to an exact program."""
        from bigdl_tpu.cli.provenance import provenance_dict
        out = {
            "buckets": ",".join(str(b) for b in self.buckets),
            **(self._shard.describe() if self._shard is not None else {}),
            "compute_dtype": (np.dtype(self.compute_dtype).name
                              if self.compute_dtype is not None
                              else "float32"),
            # shared assembly (ISSUE 18 satellite): same code path as
            # the perf JSON line and batch-predict reports
            **provenance_dict(self.module, flat=True),
            "quantize": self.quantize,
        }
        for b, m in sorted(self._bucket_mem.items()):
            # per-bucket compile-time memory (ISSUE 12): the HBM cost of
            # each program in the ladder, scrape-visible
            out[f"bucket_{b}_hbm_bytes"] = m["total_bytes"]
        ann = self.lint_annotation
        if isinstance(ann, dict):
            out["lint"] = (f"{ann.get('errors', 0)}e/"
                           f"{ann.get('warnings', 0)}w/"
                           f"{ann.get('infos', 0)}i")
        elif ann is not None:
            out["lint"] = str(ann)
        return out
