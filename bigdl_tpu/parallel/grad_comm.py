"""Compressed, bucketed, overlap-friendly gradient communication
(ISSUE 10 tentpole — ROADMAP item 2's "shrink the all-reduce" half).

The reference BigDL owed its scaling to a ``CompressedTensor`` FP16
codec over a partitioned all-reduce (parameters/FP16CompressedTensor
.scala + AllReduceParameter.scala): gradients cross the wire truncated
to 16 bits, in fixed-size slices each node reduces independently. This
module is the JAX/TPU analogue, built from three independent pieces the
strategies compose through :meth:`DataParallel.reduce_grads`:

* **Deterministic dense bucketing** — the grad pytree flattens into
  size-bounded 1-D buckets whose layout is a pure function of the param
  tree structure (leaf order, shapes, dtypes) and the byte bound:
  ``build_bucket_plan`` is host-side, cached per (treedef, shapes,
  bound), and two processes planning the same model always agree — the
  property a multi-host reduce needs, and the reason the reference
  sliced its parameter space identically on every node. Dense buckets
  also amortize per-collective latency over many small leaves
  ("Densifying Assumed-sparse Tensors", PAPERS.md: accumulate dense,
  not per-tensor).
* **Wire compression** — each bucket is cast to bf16/fp16 before the
  cross-device reduction and back to f32 after, halving wire bytes.
  ``fp16`` clamps to the finite half range first (the codec ancestor
  truncated; an Inf would poison the psum). The ``+ec`` variants add
  the rounding residual back after decompression (error compensation):
  the value the optimizer consumes is the exact f32 gradient — only
  the wire carries 16 bits — so optimizer math stays f32 by
  construction.
* **The reduction itself** — two paths:

  - under jit-SPMD (the :class:`DataParallel` compile path, params
    replicated / batch sharded) the partitioner inserts the grad
    all-reduce; :func:`apply_grad_comm` steers it by annotating the
    COMPRESSED bucket as the replication point
    (``with_sharding_constraint``) so the collective lands on the 16-bit
    value. Buckets carry no data dependencies on each other, so XLA's
    latency-hiding scheduler is free to overlap each bucket's reduce
    with backward compute that hasn't produced later buckets yet.
    Whether a given XLA build honors the dtype steering has not been
    measured on the chip (ROADMAP S7: ``collective_exposed_share.train``
    in the benchmark's ``train_dp4`` cell, compressed vs plain).
  - an explicit ``jax.shard_map`` path (:func:`compressed_psum`):
    per-bucket ``lax.psum`` over the mesh axis on the compressed value —
    the manual-collective building block for strategies that hold
    per-device partial grads (and the autotuner's measurement harness).

Bucket size is autotuned per (param-bytes, n_devices, wire-dtype) under
the ``grad_comm`` namespace of the persistent tuning cache
(:func:`bigdl_tpu.tuning.grad_bucket_bytes`); ``off`` mode and
single-device meshes bypass the transform entirely — bit-identical to
the pre-grad-comm step (the ISSUE 10 acceptance bar).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

__all__ = ["COMPRESS_MODES", "DEFAULT_BUCKET_BYTES", "GradCommConfig",
           "parse_compress_spec", "make_config", "BucketPlan",
           "build_bucket_plan", "plan_wire_bytes", "apply_grad_comm",
           "compress_bucket", "decompress_bucket", "compressed_psum"]

# the flag surface: plain 16-bit truncation or truncation + local
# error-compensation residual (see compress/decompress below)
COMPRESS_MODES = ("off", "bf16", "fp16", "bf16+ec", "fp16+ec")

# default dense-bucket byte bound before the autotuner has a decision:
# 4 MiB rides well above per-collective launch latency while keeping
# enough buckets in flight to overlap with the backward pass (the
# bucket-size sweep candidates live in tuning.autotune.GRAD_BUCKET_BYTES)
DEFAULT_BUCKET_BYTES = 4 * 2 ** 20

_F16_MAX = 65504.0  # largest finite float16


@dataclass(frozen=True)
class GradCommConfig:
    """One run's gradient-communication configuration (the parsed
    ``--gradCompress``/``--gradBuckets`` pair). ``bucket_bytes`` None
    means "auto": the tuned decision when the autotuner is on, else
    :data:`DEFAULT_BUCKET_BYTES`."""

    compress: str = "off"
    bucket_bytes: Optional[int] = None

    @property
    def active(self) -> bool:
        return self.compress != "off"

    @property
    def wire_dtype(self) -> Optional[str]:
        if not self.active:
            return None
        return "bfloat16" if self.compress.startswith("bf16") else "float16"

    @property
    def error_comp(self) -> bool:
        return self.compress.endswith("+ec")


def parse_compress_spec(spec: Optional[str]) -> str:
    """Validate one ``--gradCompress`` spelling -> canonical mode string
    (ValueError on junk; the CLI wraps it in SystemExit)."""
    mode = (spec or "off").strip().lower()
    if mode not in COMPRESS_MODES:
        raise ValueError(
            f"gradCompress must be one of {list(COMPRESS_MODES)}, "
            f"got {spec!r}")
    return mode


def make_config(compress: Optional[str] = None,
                buckets=None) -> Optional[GradCommConfig]:
    """``(--gradCompress, --gradBuckets)`` -> config (None when the whole
    surface is off). ``buckets`` is 'auto'/None or an integer MiB bound
    (ValueError on junk)."""
    mode = parse_compress_spec(compress)
    bucket_bytes = None
    if buckets is not None and str(buckets).strip().lower() != "auto":
        try:
            mib = int(str(buckets).strip())
        except ValueError:
            raise ValueError(
                f"gradBuckets must be 'auto' or an integer MiB bound, "
                f"got {buckets!r}")
        if mib < 1:
            raise ValueError(f"gradBuckets must be >= 1 MiB, got {mib}")
        bucket_bytes = mib * 2 ** 20
    if mode == "off" and bucket_bytes is None:
        return None
    return GradCommConfig(compress=mode, bucket_bytes=bucket_bytes)


# ------------------------------------------------------------ bucket plan
@dataclass(frozen=True)
class _BucketSpec:
    """One dense bucket: which flat-tree leaves it packs, in order."""
    leaf_ids: Tuple[int, ...]
    shapes: Tuple[tuple, ...]
    sizes: Tuple[int, ...]       # element counts, leaf order
    nbytes: int                  # f32 bytes of the packed bucket


@dataclass(frozen=True)
class BucketPlan:
    """Deterministic bucket layout for one grad tree. ``signature`` is a
    content hash of (leaf order, shapes, dtypes, byte bound) — two plans
    agree iff their signatures agree, the determinism contract the
    layout test asserts."""
    buckets: Tuple[_BucketSpec, ...]
    passthrough: Tuple[int, ...]  # non-float leaves, left untouched
    n_leaves: int
    bucket_bytes: int
    total_bytes: int              # f32 bytes across all bucketed leaves
    signature: str


_PLAN_CACHE: Dict[tuple, BucketPlan] = {}


def build_bucket_plan(tree, bucket_bytes: int) -> BucketPlan:
    """Flatten ``tree``'s structure into size-bounded dense buckets.

    Layout rules (all deterministic, keyed only by tree structure):
    leaves pack in ``tree_util`` flatten order; a bucket closes when the
    next leaf would push it past ``bucket_bytes`` (a single over-bound
    leaf gets its own bucket — never split, matching the reference's
    per-slice reduce granularity); non-inexact leaves (int counters)
    bypass bucketing entirely. Cached per (treedef, shapes/dtypes,
    bound) — planning is host-side trace-time work."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(getattr(l, "shape", ())) for l in leaves)
    dtypes = tuple(str(np.dtype(getattr(l, "dtype", np.float32)))
                   for l in leaves)
    key = (treedef, shapes, dtypes, int(bucket_bytes))
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        return cached

    buckets: List[_BucketSpec] = []
    passthrough: List[int] = []
    cur_ids: List[int] = []
    cur_shapes: List[tuple] = []
    cur_sizes: List[int] = []
    cur_bytes = 0

    def close():
        nonlocal cur_ids, cur_shapes, cur_sizes, cur_bytes
        if cur_ids:
            buckets.append(_BucketSpec(tuple(cur_ids), tuple(cur_shapes),
                                       tuple(cur_sizes), cur_bytes))
        cur_ids, cur_shapes, cur_sizes, cur_bytes = [], [], [], 0

    total = 0
    for i, (shape, dtname) in enumerate(zip(shapes, dtypes)):
        if not np.issubdtype(np.dtype(dtname), np.inexact):
            passthrough.append(i)
            continue
        size = int(np.prod(shape)) if shape else 1
        nbytes = size * 4  # buckets pack in f32
        total += nbytes
        if cur_bytes and cur_bytes + nbytes > bucket_bytes:
            close()
        cur_ids.append(i)
        cur_shapes.append(shape)
        cur_sizes.append(size)
        cur_bytes += nbytes
        if cur_bytes >= bucket_bytes:
            close()
    close()

    sig = hashlib.sha256(repr(
        (shapes, dtypes, int(bucket_bytes))).encode()).hexdigest()[:16]
    plan = BucketPlan(buckets=tuple(buckets),
                      passthrough=tuple(passthrough),
                      n_leaves=len(leaves), bucket_bytes=int(bucket_bytes),
                      total_bytes=total, signature=sig)
    _PLAN_CACHE[key] = plan
    return plan


def plan_wire_bytes(plan: BucketPlan, config: GradCommConfig) -> int:
    """Per-step, per-direction wire bytes the plan's buckets put on the
    interconnect (the PERF.md §17 accounting column): f32 bytes when
    compression is off, half that for a 16-bit wire dtype."""
    if not config.active:
        return plan.total_bytes
    return plan.total_bytes // 2


# ------------------------------------------------------- compress / wire
def compress_bucket(buf, mode: str):
    """f32 bucket -> wire representation. bf16 is a straight cast
    (hardware-native, the reference codec's modern spelling); fp16
    clamps to the finite half range first — the Scala codec truncated
    mantissas and could never produce Inf, and one Inf would poison the
    whole psum."""
    import jax.numpy as jnp

    if mode.startswith("fp16"):
        return jnp.clip(buf, -_F16_MAX, _F16_MAX).astype(jnp.float16)
    return buf.astype(jnp.bfloat16)


def decompress_bucket(cbuf):
    import jax.numpy as jnp

    return cbuf.astype(jnp.float32)


def compressed_psum(stacked, mesh, axis: str, mode: str):
    """Explicit compressed all-reduce of per-device partial buckets:
    ``stacked`` is (n_devices, bucket_len) with row i holding device
    i's partial f32 bucket; returns the (bucket_len,) f32 sum, reduced
    over the wire in the 16-bit dtype via an explicit per-bucket
    ``lax.psum`` inside ``jax.shard_map``. The building block for manual
    strategies holding unreduced grads, and the autotuner's measurement
    harness."""
    import jax
    from jax.sharding import PartitionSpec as P

    def local_reduce(block):
        # block: (1, L) — this device's partial bucket. Compress BEFORE
        # the wire, psum the 16-bit value, decompress after.
        c = compress_bucket(block[0], mode)
        s = jax.lax.psum(c, axis)
        return decompress_bucket(s)

    return jax.shard_map(local_reduce, mesh=mesh, in_specs=P(axis, None),
                         out_specs=P(), check_vma=False)(stacked)


# ------------------------------------------------------- the trace path
def _resolve_bucket_bytes(config: GradCommConfig, param_bytes: int,
                          n_devices: int) -> Tuple[int, str]:
    """Effective bucket byte bound + its provenance: an explicit
    --gradBuckets N wins; else the tuned ``grad_comm`` decision when the
    autotuner is on; else the shipped default."""
    if config.bucket_bytes is not None:
        return int(config.bucket_bytes), "explicit"
    from bigdl_tpu import tuning
    tuned = tuning.grad_bucket_bytes(param_bytes, n_devices,
                                     config.wire_dtype or "bfloat16")
    if tuned is not None:
        return int(tuned), "autotune"
    return DEFAULT_BUCKET_BYTES, "default"


def apply_grad_comm(grads, config: GradCommConfig, mesh=None):
    """The reduce_grads transform under jit-SPMD: bucket, compress,
    mark the compressed bucket as the replication point, decompress,
    unbucket (+ error-compensation residual). Returns ``(new_grads,
    info)`` where ``info`` is the host-side annotation dict stamped
    into perf JSON lines (n_buckets, bucket bytes + provenance, wire
    bytes vs f32 bytes, plan signature).

    Inactive config or a 1-device mesh returns ``(grads, None)``
    untouched — the traced step is then BIT-identical to the
    pre-grad-comm step (the ``--gradCompress off`` acceptance bar).

    Numerics: ``bf16``/``fp16`` feed the optimizer the decompressed
    (rounded) gradient; ``+ec`` adds the local rounding residual
    ``g - decompress(compress(g))`` back afterwards, reconstructing the
    exact f32 gradient (bf16 round-trip keeps each element within
    2^-8 relative, so the Sterbenz condition makes the residual
    subtraction exact) — optimizer math stays f32 while only the
    compressed term is annotated for the wire."""
    import jax
    import jax.numpy as jnp

    n_dev = int(getattr(mesh, "size", 0) or 0) if mesh is not None else 0
    if config is None or not config.active or n_dev <= 1:
        return grads, None

    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    param_bytes = sum(
        int(jnp.size(l)) * 4 for i, l in enumerate(leaves))
    bucket_bytes, bucket_src = _resolve_bucket_bytes(config, param_bytes,
                                                     n_dev)
    plan = build_bucket_plan(grads, bucket_bytes)

    new_leaves = list(leaves)
    for spec in plan.buckets:
        buf = jnp.concatenate(
            [jnp.ravel(leaves[i]).astype(jnp.float32)
             for i in spec.leaf_ids])
        cbuf = compress_bucket(buf, config.compress)
        # the steering annotation: tell the partitioner THIS (16-bit)
        # value is where replication happens, so the inserted
        # all-reduce rides the compressed dtype. Buckets depend only on
        # their own leaves — no cross-bucket edges — so the scheduler
        # may overlap each reduce with still-running backward compute.
        cbuf = jax.lax.with_sharding_constraint(cbuf, repl)
        dbuf = decompress_bucket(cbuf)
        if config.error_comp:
            # local error compensation: the optimizer sees the exact
            # f32 gradient; only the compressed term crossed the wire
            dbuf = dbuf + (buf - dbuf)
        offset = 0
        for leaf_id, shape, size in zip(spec.leaf_ids, spec.shapes,
                                        spec.sizes):
            piece = jax.lax.dynamic_slice_in_dim(dbuf, offset, size)
            new_leaves[leaf_id] = piece.reshape(shape).astype(
                leaves[leaf_id].dtype)
            offset += size

    info = {
        "compress": config.compress,
        "n_buckets": len(plan.buckets),
        "bucket_bytes": plan.bucket_bytes,
        "bucket_source": bucket_src,
        "wire_bytes": plan_wire_bytes(plan, config),
        "wire_bytes_f32": plan.total_bytes,
        "plan_signature": plan.signature,
        "n_devices": n_dev,
    }
    return jax.tree_util.tree_unflatten(treedef, new_leaves), info
