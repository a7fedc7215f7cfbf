"""Training loop facade (reference optim/Optimizer.scala:30-129,
DistriOptimizer.scala, LocalOptimizer.scala).

One loop for local and distributed: the reference's LocalOptimizer (clone per
core, fork-join) and DistriOptimizer (two Spark jobs per iteration, block
all-reduce) collapse into a single jitted train step; when a
:class:`~bigdl_tpu.parallel.DataParallel` strategy is supplied, the same step
is sharded over a device mesh and XLA inserts the gradient all-reduce that
the reference hand-rolls through the BlockManager (SURVEY.md §3.2).

API parity: ``Optimizer(model, dataset, criterion)`` then
``set_state/set_optim_method/set_end_when/set_validation/set_checkpoint`` and
``optimize()`` (reference setters :66-124, factory :151-186). The canonical
log line "Train N in Xs. Throughput is R records/second. Loss is L"
(DistriOptimizer.scala:241-244) is preserved.
"""

from __future__ import annotations

import logging
import math
import os
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.core.module import Module
from bigdl_tpu.core.criterion import Criterion
from bigdl_tpu.obs.spans import enabled as _obs_enabled, span as _span
from bigdl_tpu.optim.method import OptimMethod, SGD
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.triggers import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.resilience.faults import hook as _fault_hook
from bigdl_tpu.utils.file import (save_pytree, load_pytree,
                                  exists as file_exists)

logger = logging.getLogger("bigdl_tpu")

__all__ = ["Optimizer", "TrainedModel"]


def _canon_ckpt_path(p: str) -> str:
    """Spelling-insensitive checkpoint path identity (ADVICE r5 #3): a
    trailing slash or relative-vs-absolute difference between the
    resume() dir and the set_checkpoint() dir must not disable the
    orphan-overwrite allowance (which would kill resume with
    FileExistsError at the first re-reached snapshot name). Remote URLs
    only get redundant slashes collapsed — abspath would mangle the
    scheme."""
    p = str(p)
    if "://" in p:
        scheme, rest = p.split("://", 1)
        return scheme + "://" + "/".join(s for s in rest.split("/") if s)
    return os.path.abspath(os.path.normpath(p))


class TrainedModel:
    """What optimize() returns: the module description plus trained pytrees."""

    def __init__(self, module: Module, params, mod_state):
        self.module = module
        self.params = params
        self.mod_state = mod_state

    def predict(self, x, batch_size: Optional[int] = None):
        return self.module.forward(self.params, x, self.mod_state,
                                   training=False)


class Optimizer:
    def __init__(self, model: Module, dataset, criterion: Criterion,
                 optim_method: Optional[OptimMethod] = None,
                 end_when: Optional[Trigger] = None,
                 strategy=None, seed: int = 42, log_every: int = 1,
                 compute_dtype=None, accum_steps: int = 1,
                 nan_check: bool = True, aux_loss_weight: float = 0.01,
                 steps_per_dispatch: int = 1):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method = optim_method or SGD(learning_rate=1e-2)
        self.end_when = end_when or Trigger.max_epoch(1)
        self.strategy = strategy  # None => single-device
        self.seed = seed
        # bf16 activations/grad math with fp32 params+loss — the native
        # replacement for the reference's truncated-fp16 gradient codec
        # (parameters/FP16CompressedTensor.scala)
        self.compute_dtype = compute_dtype
        # accum_steps > 1: each optimizer update averages grads over that
        # many microbatches (batch_size must be divisible by it)
        self.accum_steps = accum_steps
        # NaN guard at every log point (SURVEY.md §5: functional purity
        # removes the reference's race class; divergence detection is the
        # failure mode left worth watching). Free: piggybacks on the loss
        # sync the log line already pays for.
        self.nan_check = nan_check
        # modules may surface auxiliary losses through their state tree as
        # scalar leaves named "aux_loss" (nn.MoE load balancing); they are
        # added to the criterion loss with this weight (Switch Transformer's
        # 0.01 default). Set 0.0 to disable.
        self.aux_loss_weight = aux_loss_weight
        # steps_per_dispatch > 1: lax.scan K optimizer steps over K
        # prefetched batches inside ONE jitted program, amortizing the
        # per-dispatch host overhead (measured +1.6% ResNet-50
        # throughput at K=10 on the pre-PR-1 chip set-up, PERF.md).
        # Update math and the per-step RNG sequence are
        # IDENTICAL to K dispatches (keys are pre-split host-side);
        # iteration-counted triggers fire at the first dispatch boundary
        # at or after their threshold (Trigger.several_iteration is
        # crossing-based). Single-device path only: under a distributed
        # strategy the per-dispatch overhead is already pipelined by the
        # multi-controller runtime and batches arrive pre-sharded.
        self.steps_per_dispatch = int(steps_per_dispatch)
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        if self.steps_per_dispatch > 1 and strategy is not None:
            raise ValueError(
                "steps_per_dispatch > 1 is a single-device dispatch "
                "amortization; it cannot be combined with a distributed "
                "strategy (whose runtime pipelines dispatch already)")
        self._val_trigger = None
        self._val_dataset = None
        self._val_methods: Sequence[ValidationMethod] = ()
        self._ckpt_trigger = None
        self._ckpt_path = None
        self._init_params = None
        self._init_mod_state = None
        self._init_opt_state = None
        self.metrics = Metrics()
        # log_every > 1 avoids the per-step host<->device loss sync on the
        # hot path (the float() below blocks until the step finishes, which
        # serializes dispatch on TPU)
        self.log_every = max(1, log_every)
        self._last_val_iter = -1
        self._last_ckpt_iter = -1
        # step-phase accounting (ISSUE 7): cumulative seconds per phase
        # (obs.metrics.TRAIN_PHASES taxonomy). data_wait/dispatch/ckpt
        # are metered in EVERY run (the measurements were already being
        # taken — the reported feed-stall gap, PERF.md §4, was dropped on
        # the floor); h2d and the true device wait need a per-step sync
        # and are only split out when the span tracer is on (--obs).
        self._phase_totals: dict = {}
        self._obs_hists = None
        self._obs_capture = None  # CaptureController (cli wiring)

    # ---------------------------------------------------------------- setters
    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod]) -> "Optimizer":
        """(reference Optimizer.setValidation :97-105)"""
        self._val_trigger = trigger
        self._val_dataset = dataset
        self._val_methods = list(methods)
        return self

    def set_checkpoint(self, trigger: Trigger, path: str,
                       overwrite: bool = False,
                       sharded: bool = False,
                       async_save: bool = False,
                       keep_last: Optional[int] = None) -> "Optimizer":
        """(reference Optimizer.setCheckpoint :87-94 +
        overWriteCheckpoint flag: refuse to clobber an existing snapshot
        unless ``overwrite``). ``sharded=True`` writes orbax shards
        directly from each host instead of gathering to one blob —
        the pod-scale path (utils/orbax_ckpt.py). ``async_save=True``
        snapshots the pytrees to host memory and serializes on a
        background thread, so the step loop only pays the device->host
        copy, not the disk/remote write (single-blob path only; a prior
        in-flight write is joined — and its errors re-raised — before
        the next snapshot starts and at the end of optimize()).
        ``keep_last=k`` garbage-collects older snapshots after each
        write, never deleting the newest checksum-VALID pair
        (utils/file.gc_checkpoints)."""
        if async_save and sharded:
            raise ValueError("async_save supports the single-blob path; "
                             "orbax sharded writes are per-host streaming "
                             "already")
        if keep_last is not None and keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self._ckpt_trigger = trigger
        self._ckpt_path = path
        self._ckpt_overwrite = overwrite
        self._ckpt_sharded = sharded
        self._ckpt_async = async_save
        self._ckpt_keep_last = keep_last
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm: float
                                         ) -> "Optimizer":
        """Global-L2-norm gradient clipping before the optimizer update
        (reference Optimizer.setGradientClippingByl2Norm)."""
        self._clip_norm = float(max_norm)
        return self

    def set_constant_gradient_clipping(self, lo: float, hi: float
                                       ) -> "Optimizer":
        """Elementwise gradient clipping to [lo, hi] (reference
        Optimizer.setConstantGradientClipping)."""
        self._clip_const = (float(lo), float(hi))
        return self

    def set_state(self, params=None, mod_state=None,
                  opt_state=None) -> "Optimizer":
        """Warm-start from explicit pytrees (reference setState :66 +
        --model/--state resume flags)."""
        self._init_params = params
        self._init_mod_state = mod_state
        self._init_opt_state = opt_state
        return self

    def resume(self, checkpoint_dir: str) -> "Optimizer":
        """Load the newest model.<n>/state.<n> pair from a directory
        (either single-blob or orbax-sharded snapshots).

        Step-equivalence (ADVICE r5 #4): snapshots written by this
        version also carry the host-RNG split count, the records consumed
        in the open epoch, and the completed-epoch count; optimize() then
        fast-forwards the PRNG stream, skips the already-consumed leading
        records of the interrupted epoch, and replays the per-epoch
        ``dataset.shuffle()`` calls — so for datasets whose order is
        driven by a seeded ``shuffle()`` (BatchDataSet, LocalArrayDataSet
        and friends), kill+resume replays exactly the dropout keys and
        batches an uninterrupted run would have used. Residual
        non-equivalence: datasets that advance their own RNG inside
        ``__iter__`` (e.g. LocalArrayDataSet(shuffle=True)) or stream
        from non-deterministic sources re-order the skipped records, and
        older snapshots without the counters resume with a fresh stream
        from the seed (counters-only semantics, as before)."""
        from bigdl_tpu.utils.file import (isdir, latest_checkpoint,
                                          latest_valid_checkpoint_pair,
                                          verify_checkpoint)
        # newest MATCHED *VALID* pair: a kill between the model.<n> and
        # state.<n> writes must not mix params from n with optimizer
        # state from n-k, and a checksum-mismatched (torn/bit-rotted)
        # pair must fall back to the previous one instead of crashing at
        # deserialize (ISSUE 6: recovery costs one checkpoint interval,
        # not the run)
        with _span("ckpt_restore", dir=str(checkpoint_dir)):
            m, s = latest_valid_checkpoint_pair(checkpoint_dir)
            if m is None:
                # accept a model-only snapshot (predict/eval-style dirs
                # with no optimizer state at all) — still checksum-gated
                m = latest_checkpoint(checkpoint_dir, "model.")
                s = None
                if m is not None and not verify_checkpoint(m):
                    from bigdl_tpu.resilience.faults import ChecksumError
                    raise ChecksumError(
                        f"the only snapshot in {checkpoint_dir} ({m}) "
                        f"fails checksum verification and there is no "
                        f"earlier one to fall back to")
            if m and isdir(m):  # orbax checkpoints are directories
                from bigdl_tpu.utils.orbax_ckpt import restore_sharded
                blob = restore_sharded(m)
                self._init_params = blob["params"]
                self._init_mod_state = blob["mod_state"]
                self._set_resume_driver(blob, m)
                if s:
                    self._init_opt_state = restore_sharded(s)
                return self
            if m:
                blob = load_pytree(m)
                self._init_params = blob["params"]
                self._init_mod_state = blob["mod_state"]
                self._set_resume_driver(blob, m)
            if s:
                self._init_opt_state = load_pytree(s)
            return self

    def _set_resume_driver(self, blob, model_path: str) -> None:
        """Resumed training continues the epoch/iteration numbering
        (reference semantics: maxEpoch/maxIteration are CUMULATIVE across
        resume, checkpoint files keep ascending names, and harnesses can
        compare pre-kill vs post-resume progress — soak finding, round
        5). Newer snapshots carry the counters in the blob; older ones
        fall back to the iteration encoded in the ``model.<n>`` name."""
        drv = blob.get("driver")
        if drv is None:
            tail = str(model_path).rstrip("/").rsplit(".", 1)[-1]
            if tail.isdigit():
                drv = {"iteration": int(tail)}
        if drv:
            self._resume_driver = {k: int(v) for k, v in dict(drv).items()
                                   if k in ("epoch", "iteration",
                                            "rng_splits", "epoch_records")}
            saved_plan = dict(drv).get("plan")
            if saved_plan:
                # blob round-trip turns scalars into 0-d arrays; epoch is
                # expected to differ (the snapshot's cursor, not identity)
                theirs = {k: (v.item() if hasattr(v, "item") else v)
                          for k, v in dict(saved_plan).items()
                          if k != "epoch"}
                cur = getattr(self.dataset, "plan", None)
                if cur is not None and hasattr(cur, "signature"):
                    mine = {k: v for k, v in cur.signature().items()
                            if k != "epoch"}
                    if mine != theirs:
                        logger.warning(
                            "resume: checkpoint epoch plan %s differs "
                            "from this run's %s — the replayed batch "
                            "stream will NOT match the killed run's",
                            theirs, mine)
            # a kill between the model.<n> and state.<n> writes leaves an
            # unmatched (unusable) newer snapshot; with counters resuming,
            # the deterministic trigger will re-reach exactly that name —
            # allow overwriting those specific paths without the global
            # overwrite flag
            it = self._resume_driver.get("iteration")
            if it is not None:
                from bigdl_tpu.utils.file import orphaned_snapshots
                d = os.path.dirname(str(model_path).rstrip("/"))
                # canonicalized so the later membership test is immune to
                # trailing-slash / relative-vs-absolute spelling drift
                # between resume() and set_checkpoint() (ADVICE r5 #3)
                orphans = {_canon_ckpt_path(o)
                           for o in orphaned_snapshots(d, it)}
                if orphans:
                    logger.warning(
                        "resume: %d unmatched snapshot file(s) newer than "
                        "the loaded pair (unclean shutdown mid-write); the "
                        "resumed run may overwrite them: %s",
                        len(orphans), sorted(orphans))
                self._resume_orphans = orphans

    # ---------------------------------------------------------------- build
    def _build_step(self):
        # conv-layout decision for this device AND dispatch configuration
        # (PERF.md §8.2/§9; no-op when a --convLayout/API policy is
        # already installed). The measured decision is positive on the
        # plain path but negative chained with multi-step dispatch
        # (window-2 combination matrix), so the K>1 variant resolves its
        # own key — installing the all-NHWC default until a measurement
        # exists, instead of skipping and leaking a previous K=1 install
        # (ADVICE r5 #1)
        from bigdl_tpu import tuning
        tuning.install_conv_layouts(
            "inner" if self.steps_per_dispatch > 1 else "plain")

        model, criterion, opt = self.model, self.criterion, self.optim_method

        dtype = self.compute_dtype
        accum = max(1, self.accum_steps)
        aux_w = self.aux_loss_weight

        def sum_aux_losses(state):
            # modules surface auxiliary losses as scalar "aux_loss" state
            # leaves (nn/moe.py); collect them so Optimizer-driven training
            # gets load balancing without a hand-written step
            total = jnp.zeros((), jnp.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(state):
                last = path[-1] if path else None
                if (isinstance(last, jax.tree_util.DictKey)
                        and last.key == "aux_loss"):
                    total = total + leaf.astype(jnp.float32)
            return total

        def grads_of(params, mod_state, x, y, rng):
            if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(dtype)

            def loss_fn(p):
                out, new_ms = model.apply(p, mod_state, x,
                                          training=True, rng=rng)
                if dtype is not None:  # fp32 loss/softmax
                    out = jax.tree_util.tree_map(
                        lambda o: o.astype(jnp.float32), out)
                loss = criterion(out, y)
                if aux_w:
                    loss = loss + aux_w * sum_aux_losses(new_ms)
                return loss, new_ms

            (loss, new_ms), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, new_ms, grads

        def train_step(params, mod_state, opt_state, x, y, rng):
            if accum == 1:
                loss, new_ms, grads = grads_of(params, mod_state, x, y, rng)
            else:
                # gradient accumulation: the batch is split into `accum`
                # microbatches scanned inside ONE jitted step — same HBM
                # profile as a small batch, same update as the large one
                if x.shape[0] % accum:
                    raise ValueError(
                        f"batch size {x.shape[0]} not divisible by "
                        f"accum_steps={accum}")
                xm = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                ym = y.reshape((accum, y.shape[0] // accum) + y.shape[1:])

                def body(carry, mb):
                    ms, g_acc, l_acc, i = carry
                    xb, yb = mb
                    r = jax.random.fold_in(rng, i)
                    loss, ms, grads = grads_of(params, ms, xb, yb, r)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                    return (ms, g_acc, l_acc + loss, i + 1), None

                g0 = jax.tree_util.tree_map(
                    lambda p_: jnp.zeros(p_.shape, jnp.float32), params)
                (new_ms, grads, loss, _), _ = jax.lax.scan(
                    body, (mod_state, g0, jnp.zeros((), jnp.float32), 0),
                    (xm, ym))
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = loss / accum
            if self.strategy is not None:
                grads, loss = self.strategy.reduce_grads(grads, loss)
            clip_const = getattr(self, "_clip_const", None)
            if clip_const is not None:
                from bigdl_tpu.optim.method import clip_by_value
                grads = clip_by_value(grads, *clip_const)
            clip_norm = getattr(self, "_clip_norm", None)
            if clip_norm is not None:
                from bigdl_tpu.optim.method import clip_by_global_norm
                grads, _ = clip_by_global_norm(grads, clip_norm)
            new_params, new_opt = opt.update(grads, opt_state, params)
            return new_params, new_ms, new_opt, loss

        if self.strategy is not None:
            mesh = getattr(self.strategy, "mesh", None)
            n_dev = mesh.size if mesh is not None else jax.device_count()
            from bigdl_tpu.nn.norm import unfuse_bn_for_spmd
            unfused = unfuse_bn_for_spmd(self.model, n_dev)
            if unfused:
                logger.warning(
                    "fused BN disabled on %d module(s): pallas_call has no "
                    "GSPMD partitioning rule, so the single-read stats "
                    "kernel would replicate sharded activations under the "
                    "%d-device mesh (jnp stats path used instead)",
                    unfused, n_dev)
            return self.strategy.compile_step(train_step), None
        step = jax.jit(train_step, donate_argnums=(0, 1, 2))
        chunk = None
        if self.steps_per_dispatch > 1:
            # K steps scanned inside one program over K stacked batches +
            # K pre-split rng keys; returns the LAST step's loss (what K
            # sequential dispatches would have left in driver["loss"])
            def chunk_step(params, mod_state, opt_state, xs, ys, keys):
                def body(carry, inp):
                    p, m, o = carry
                    xb, yb, kb = inp
                    p, m, o, loss = train_step(p, m, o, xb, yb, kb)
                    return (p, m, o), loss

                (p, m, o), losses = jax.lax.scan(
                    body, (params, mod_state, opt_state), (xs, ys, keys))
                return p, m, o, losses[-1]

            chunk = jax.jit(chunk_step, donate_argnums=(0, 1, 2))
        return step, chunk

    def _build_eval(self):
        from bigdl_tpu.optim.validator import build_eval_fn
        return build_eval_fn(self.model, self._val_methods, self.strategy)

    # ------------------------------------------------------------ obs phases
    def _obs_phase(self, name: str, dt: float) -> None:
        """Account ``dt`` seconds to a step phase: always into the
        cumulative totals (a dict add), and into the shared registry's
        per-step histograms when --obs is on."""
        self._phase_totals[name] = self._phase_totals.get(name, 0.0) + dt
        h = self._obs_hists
        if h is not None:
            hist = h.get(name)
            if hist is not None:
                hist.observe(dt * 1000.0)

    def phase_totals(self) -> dict:
        """Cumulative per-phase seconds for this run — what the perf
        harness stamps as the ``*_s`` phase columns (ISSUE 7)."""
        return dict(self._phase_totals)

    def set_capture(self, controller) -> "Optimizer":
        """Attach an :class:`~bigdl_tpu.obs.capture.CaptureController`;
        ``on_step`` is driven once per dispatch (--traceSteps/SIGUSR2/
        touch-file mid-run profile windows)."""
        self._obs_capture = controller
        return self

    # -------------------------------------------------------------- optimize
    def optimize(self) -> TrainedModel:
        # per-run conv-policy isolation (ADVICE r5 #1): _build_step
        # installs a layout decision for THIS run's dispatch config; the
        # pre-run policy comes back afterwards so a later run in the same
        # process starts clean
        from bigdl_tpu.ops.conv2d import policy_snapshot, restore_policy
        snap = policy_snapshot()
        try:
            return self._optimize()
        finally:
            restore_policy(snap)

    def _optimize(self) -> TrainedModel:
        rng = jax.random.PRNGKey(self.seed)
        # every consumption of the host PRNG stream goes through _next_key
        # so its position is a single counter — checkpointed, and
        # fast-forwarded on resume (ADVICE r5 #4: kill+resume replays the
        # exact dropout/rng keys of an uninterrupted run)
        self._rng_splits = 0

        def _next_key():
            nonlocal rng
            rng, k = jax.random.split(rng)
            self._rng_splits += 1
            return k

        k_init = _next_key()
        params = (self._init_params if self._init_params is not None
                  else self.model.init(k_init))
        mod_state = (self._init_mod_state if self._init_mod_state is not None
                     else self.model.init_state())
        opt_state = (self._init_opt_state if self._init_opt_state is not None
                     else self.optim_method.init(params))
        if self.strategy is not None:
            params, mod_state, opt_state = self.strategy.place(
                params, mod_state, opt_state)

        step_fn, chunk_fn = self._build_step()
        eval_fn = self._build_eval() if self._val_methods else None

        # --obs: per-step phase histograms flow into the shared registry
        # (scraped live by the --metricsPort listener). Tracing adds no
        # device sync: the device phase is the wait inside the loss fetch
        # that the loop makes at every log point anyway
        if _obs_enabled():
            from bigdl_tpu.obs.metrics import get_registry, phase_histograms
            self._obs_hists = phase_histograms(get_registry(), "train")
        capture = self._obs_capture

        driver = {"epoch": 1, "iteration": 0, "prev_iteration": 0,
                  "epoch_finished": False, "loss": float("inf")}
        rd = getattr(self, "_resume_driver", None)
        self._skip_records = 0
        if rd:
            driver["iteration"] = rd.get("iteration", 0)
            driver["prev_iteration"] = driver["iteration"]
            driver["epoch"] = rd.get("epoch", 1)
            # step-equivalent resume (ADVICE r5 #4): put the PRNG stream,
            # the per-epoch shuffle chain, and the data cursor back where
            # the killed process left them. Older snapshots carry no
            # counters and keep the counters-only behavior.
            while self._rng_splits < rd.get("rng_splits", 0):
                _next_key()
            for _ in range(driver["epoch"] - 1):  # one shuffle per rollover
                self.dataset.shuffle()
            self._skip_records = rd.get("epoch_records", 0)
            logger.info("Resuming at epoch %d, iteration %d (rng stream at "
                        "%d splits, skipping %d consumed records)",
                        driver["epoch"], driver["iteration"],
                        self._rng_splits, self._skip_records)
        wall_start = time.time()
        self._wall_start = wall_start
        records_this_epoch = 0
        _end = object()  # end-of-epoch sentinel (None could be a real batch)
        last_log_t = time.time()
        fetch_accum = 0.0
        # a model that declares training counters returns this step's in
        # its state ("train_counts"); they ride with the loss in one fetch
        # and are added to the shared registry
        declared = getattr(self.model, "train_counters", None)
        if declared:
            from bigdl_tpu.obs.metrics import get_registry
            counters = {name: get_registry().counter(name, help)
                        for name, help in declared.items()}

        def after_dispatch(n_rec, n_iters, t0, loss, state):
            """Advance counters and emit the log point after one dispatch
            (one step, or a steps_per_dispatch chunk of n_iters steps);
            at a log point the model's declared counters count the step
            that ended there."""
            nonlocal last_log_t, fetch_accum, records_this_epoch
            prev_it = driver["iteration"]
            driver["prev_iteration"] = prev_it
            driver["iteration"] = prev_it + n_iters
            # keep `loss` a device array between log points so dispatch
            # N+1 can be enqueued while N still runs on device
            driver["loss"] = loss
            records_this_epoch += n_rec
            driver["epoch_records"] = records_this_epoch  # resume cursor
            # crossing-based (== modulo for n_iters=1): a chunk that jumps
            # the counter past a multiple of log_every still logs
            if driver["iteration"] // self.log_every != prev_it // self.log_every:
                t_w = time.perf_counter()
                with _span("loss_fetch"):
                    if declared:
                        loss, counts = jax.device_get(
                            (loss, state["train_counts"]))
                        for name, n in counts.items():
                            counters[name].inc(int(n))
                    loss_f = float(loss)
                self._obs_phase("device", time.perf_counter() - t_w)
                driver["loss"] = loss_f
                if self.nan_check and not math.isfinite(loss_f):
                    raise FloatingPointError(
                        f"loss became {loss_f} at iteration "
                        f"{driver['iteration']} (epoch "
                        f"{driver['epoch']}) — NaN guard tripped; last "
                        f"checkpoint is the recovery point")
                dt = time.time() - t0
                # both counters cover the SAME interval (since the last
                # log point), so their sums are comparable: host wall
                # time = batch fetch + compute/dispatch/device wait
                now = time.time()
                self.metrics.add("get batch time", fetch_accum)
                self.metrics.add("computing time",
                                 (now - last_log_t) - fetch_accum)
                last_log_t, fetch_accum = now, 0.0
                logger.info(
                    "Train %d in %.4fs. Throughput is %.1f "
                    "records/second. Loss is %.4f",
                    n_rec, dt, n_rec / max(dt, 1e-9), loss_f)
                self._summary_write("train", {
                    "iteration": driver["iteration"],
                    "epoch": driver["epoch"],
                    "loss": loss_f,
                    "records_per_second": n_rec / max(dt, 1e-9)})
                # reference logs metrics.summary() at debug each
                # iteration (DistriOptimizer.scala:245); guard so the
                # string is only built when it will be emitted
                if logger.isEnabledFor(logging.DEBUG):
                    logger.debug("%s", self.metrics.summary())

        def _shape_sig(b):
            bx, by = b
            return (np.shape(bx), tuple(
                np.shape(l) for l in jax.tree_util.tree_leaves(by)))

        K = self.steps_per_dispatch
        while not self.end_when(driver):
            driver["epoch_finished"] = False
            epoch_start = time.time()
            ph_snap = dict(self._phase_totals)  # epoch-delta baseline
            records_this_epoch = 0
            driver["epoch_records"] = 0
            opt_state = self.optim_method.set_epoch(opt_state, driver["epoch"])
            data_iter = iter(self.dataset)
            if self._skip_records:
                # mid-epoch resume: drop the leading records the killed
                # process already trained on, so the epoch continues at
                # the same cursor instead of replaying from its start
                skip, self._skip_records = self._skip_records, 0
                skipped = 0
                while skipped < skip:
                    b = next(data_iter, _end)
                    if b is _end:
                        break
                    bx, _by = b
                    skipped += len(bx)
                records_this_epoch = skipped
                driver["epoch_records"] = skipped
            pending = None  # batch fetched but shape-incompatible w/ chunk
            epoch_done = False
            while not epoch_done:
                with _span("train_step", step=driver["iteration"]):
                    # fetch one dispatch group: a single batch (K=1), or up
                    # to K same-shape batches to scan inside one program
                    t_fetch = time.time()
                    buf = []
                    with _span("data_wait"):
                        while len(buf) < K:
                            if pending is not None:
                                b, pending = pending, None
                            else:
                                b = next(data_iter, _end)
                                if b is not _end:
                                    _fault_hook("data")  # one visit per fetch
                            if b is _end:
                                epoch_done = True
                                break
                            if buf and _shape_sig(b) != _shape_sig(buf[0]):
                                pending = b  # ragged tail: flush, retry next
                                break
                            buf.append(b)
                    dt_fetch = time.time() - t_fetch
                    fetch_accum += dt_fetch
                    self._obs_phase("data_wait", dt_fetch)
                    if not buf:
                        break
                    if chunk_fn is not None and len(buf) == K:
                        if capture is not None:
                            capture.on_step(driver["iteration"])
                        t0 = time.time()
                        t_h = time.perf_counter()
                        with _span("h2d", batches=K):
                            xs = jnp.stack([jnp.asarray(bx) for bx, _ in buf])
                            ys = jax.tree_util.tree_map(
                                lambda *ls: jnp.stack(
                                    [jnp.asarray(l) for l in ls]),
                                *[by for _, by in buf])
                        self._obs_phase("h2d", time.perf_counter() - t_h)
                        # fault site BEFORE the dispatch and BEFORE the rng
                        # splits: a preemption here loses the whole chunk,
                        # exactly like a kill between dispatches would
                        _fault_hook("step")
                        # same host key sequence as K=1 (counted for resume)
                        keys = [_next_key() for _ in range(K)]
                        t_d = time.perf_counter()
                        try:
                            with _span("dispatch", steps=K):
                                params, mod_state, opt_state, loss = chunk_fn(
                                    params, mod_state, opt_state, xs, ys,
                                    jnp.stack(keys))
                        except Exception as e:
                            # RESOURCE_EXHAUSTED autopsy (ISSUE 12): write
                            # the MemoryReport to --traceDir + fault log,
                            # then crash exactly as before
                            from bigdl_tpu.obs import memory as _obs_mem
                            _obs_mem.handle_oom(e, "train_dispatch")
                            raise
                        self._obs_phase("dispatch", time.perf_counter() - t_d)
                        after_dispatch(sum(len(bx) for bx, _ in buf), K, t0,
                                       loss, mod_state)
                        self._maybe_validate(eval_fn, params, mod_state,
                                             driver)
                        self._maybe_checkpoint(params, mod_state, opt_state,
                                               driver)
                        if self.end_when(driver):
                            break
                        continue
                    for x, y in buf:  # K == 1, or a ragged/short group
                        if capture is not None:
                            capture.on_step(driver["iteration"])
                        t0 = time.time()
                        # fault site before the step's rng split + dispatch:
                        # a preemption loses this step, as a real kill would
                        _fault_hook("step")
                        t_h = time.perf_counter()
                        with _span("h2d"):
                            if isinstance(x, jax.Array):
                                # staged upstream (pipeline --stage device):
                                # the batch is already committed to device
                                # (and to the strategy's sharded layout) —
                                # dispatch no longer pays the h2d copy
                                pass
                            elif self.strategy is not None:
                                x, y = self.strategy.shard_batch(x, y)
                            else:
                                # target may be a pytree (Mixup's
                                # (y_a, y_b, lam))
                                x = jnp.asarray(x)
                                y = jax.tree_util.tree_map(jnp.asarray, y)
                        self._obs_phase("h2d", time.perf_counter() - t_h)
                        k_step = _next_key()
                        t_d = time.perf_counter()
                        try:
                            with _span("dispatch"):
                                params, mod_state, opt_state, loss = step_fn(
                                    params, mod_state, opt_state, x, y,
                                    k_step)
                        except Exception as e:
                            from bigdl_tpu.obs import memory as _obs_mem
                            _obs_mem.handle_oom(e, "train_dispatch")
                            raise
                        self._obs_phase("dispatch", time.perf_counter() - t_d)
                        after_dispatch(len(x), 1, t0, loss, mod_state)
                        self._maybe_validate(eval_fn, params, mod_state,
                                             driver)
                        self._maybe_checkpoint(params, mod_state, opt_state,
                                               driver)
                        if self.end_when(driver):
                            epoch_done = True
                            break
            driver["epoch"] += 1
            driver["epoch_finished"] = True
            driver["epoch_records"] = 0  # next epoch starts at cursor 0
            self.dataset.shuffle()
            dt_e = time.time() - epoch_start
            # surface the phase split EVERY epoch (ISSUE 7 satellite: the
            # old fetch_accum was measured then dropped — the feed-stall
            # gap behind resnet50_pipe's 0.99% MFU, PERF.md §4, was
            # invisible in normal runs). Every phase meters in every run;
            # device is the wait inside the loss fetch at each log point.
            d_wait = (self._phase_totals.get("data_wait", 0.0)
                      - ph_snap.get("data_wait", 0.0))
            d_disp = (self._phase_totals.get("dispatch", 0.0)
                      - ph_snap.get("dispatch", 0.0))
            logger.info(
                "Epoch %d done: %d records in %.2fs (%.1f rec/s; "
                "data_wait %.2fs, dispatch %.2fs, feed stall %.1f%%)",
                driver["epoch"] - 1, records_this_epoch, dt_e,
                records_this_epoch / max(dt_e, 1e-9), d_wait, d_disp,
                100.0 * d_wait / max(dt_e, 1e-9))
            # cumulative phase seconds into the shared registry — live
            # on the --metricsPort listener, or read post-hoc by callers
            from bigdl_tpu.obs.metrics import TRAIN_PHASES, get_registry
            _reg = get_registry()
            for _ph in TRAIN_PHASES:
                d = (self._phase_totals.get(_ph, 0.0)
                     - ph_snap.get(_ph, 0.0))
                if d > 0.0:
                    _reg.counter(
                        f"train_phase_{_ph}_seconds_total",
                        f"cumulative {_ph} phase seconds").inc(d)
            if jax.process_count() > 1:
                # reference driver logs "computing time for each node"
                # via Spark accumulators (Metrics.scala:25-117); the
                # aggregate is a collective, so it runs UNCONDITIONALLY
                # on every host (a log-level guard could deadlock gloo)
                logger.info("%s", self.metrics.summary(aggregate=True))
            self._maybe_validate(eval_fn, params, mod_state, driver)
            self._maybe_checkpoint(params, mod_state, opt_state, driver)

        self._join_ckpt_writer()  # drain any in-flight async write
        logger.info("Training finished after %d iterations in %.1fs",
                    driver["iteration"], time.time() - wall_start)
        return TrainedModel(self.model, params, mod_state)

    # ------------------------------------------------------------- callbacks
    def _maybe_validate(self, eval_fn, params, mod_state, driver):
        if (eval_fn is None or self._val_trigger is None
                or not self._val_trigger(driver)
                or driver["iteration"] == self._last_val_iter):
            return None
        self._last_val_iter = driver["iteration"]
        from bigdl_tpu.optim.validator import run_evaluation
        results = run_evaluation(eval_fn, self._val_dataset,
                                 self._val_methods, params, mod_state,
                                 self.strategy)
        for m, r in zip(self._val_methods, results):
            logger.info("%s is %r", m.name, r)
        self._summary_write("val", {
            "iteration": driver["iteration"],
            "epoch": driver["epoch"],
            **{m.name.replace(" ", "_"): r.result()[0]
               for m, r in zip(self._val_methods, results)}})
        driver["val_results"] = results
        if results:
            # first method's scalar drives Trigger.max_score (time-to-acc)
            driver["val_score"] = float(results[0].result()[0])
        return results

    # -------------------------------------------------------- summaries
    def set_summary(self, directory: str) -> "Optimizer":
        """Append per-log-point train scalars and per-validation metric
        values as JSON lines to <dir>/train.jsonl and <dir>/val.jsonl —
        the plottable training-curve record (the observability the
        reference left to log scraping)."""
        os.makedirs(directory, exist_ok=True)
        self._summary_dir = directory
        return self

    def _summary_write(self, which: str, row: dict) -> None:
        d = getattr(self, "_summary_dir", None)
        if d is None:
            return
        import json
        start = getattr(self, "_wall_start", None)
        if start is not None:  # accuracy-vs-wall-clock curves need time
            row = {**row, "wall_s": round(time.time() - start, 3)}
        with open(os.path.join(d, f"{which}.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")

    def _maybe_checkpoint(self, params, mod_state, opt_state, driver):
        if (self._ckpt_path is None or self._ckpt_trigger is None
                or not self._ckpt_trigger(driver)
                or driver["iteration"] == self._last_ckpt_iter):
            return
        # ckpt phase: what the loop thread pays for this snapshot (the
        # async path only pays the device->host copy here; the disk
        # write runs on the worker and is not loop-thread stall)
        t_ck = time.perf_counter()
        try:
            with _span("ckpt", iteration=driver["iteration"]):
                self._write_checkpoint(params, mod_state, opt_state,
                                       driver)
        finally:
            self._obs_phase("ckpt", time.perf_counter() - t_ck)

    def _write_checkpoint(self, params, mod_state, opt_state, driver):
        self._last_ckpt_iter = driver["iteration"]
        n = driver["iteration"]
        target = os.path.join(self._ckpt_path, f"model.{n}")
        overwrite = (getattr(self, "_ckpt_overwrite", False)
                     or _canon_ckpt_path(target)
                     in getattr(self, "_resume_orphans", ()))
        if file_exists(target) and not overwrite:
            raise FileExistsError(
                f"{target} exists; pass overwrite=True to set_checkpoint "
                f"(--overWriteCheckpoint) to clobber it")
        drv = {"epoch": driver["epoch"], "iteration": n,
               # step-equivalent resume counters (ADVICE r5 #4): the host
               # PRNG stream position and the records already consumed in
               # the open epoch (0 at an epoch boundary)
               "rng_splits": int(getattr(self, "_rng_splits", 0)),
               "epoch_records": int(driver.get("epoch_records", 0))}
        plan = getattr(self.dataset, "plan", None)
        if plan is not None and hasattr(plan, "signature"):
            # the executor feed's epoch-plan signature: resume verifies
            # the replayed batch schedule matches the killed run's
            drv["plan"] = plan.signature()
        if getattr(self, "_ckpt_sharded", False):
            # pod-scale path: every host writes its own shards, no gather
            from bigdl_tpu.utils.orbax_ckpt import save_sharded
            save_sharded({"params": params, "mod_state": mod_state,
                          "driver": drv},
                         target, overwrite=overwrite)
            save_sharded(opt_state,
                         os.path.join(self._ckpt_path, f"state.{n}"),
                         overwrite=overwrite)
        else:
            layout = None
            if self.strategy is not None:
                params, mod_state, opt_state = self.strategy.gather(
                    params, mod_state, opt_state)
                # dp layout signature for the topology manifest: the
                # blobs below hold gathered LOGICAL arrays, so a later
                # resume may re-place them into any mesh (ISSUE 11)
                sig = getattr(self.strategy, "layout_signature", None)
                if sig is not None:
                    layout = sig()
            state_target = os.path.join(self._ckpt_path, f"state.{n}")
            if getattr(self, "_ckpt_async", False):
                self._join_ckpt_writer()  # one in-flight write at a time
                # device->host snapshot on the loop thread (cheap, and the
                # arrays must be frozen before the next step mutates them);
                # serialization + IO move to the worker
                snap_model = jax.device_get(
                    {"params": params, "mod_state": mod_state,
                     "driver": drv})
                snap_opt = jax.device_get(opt_state)

                def _write():
                    save_pytree(snap_model, target, layout=layout)
                    save_pytree(snap_opt, state_target, layout=layout)
                    self._gc_ckpts()
                    logger.info("Checkpoint written at iteration %d to %s "
                                "(async)", n, self._ckpt_path)

                import threading
                self._ckpt_thread = threading.Thread(
                    target=self._ckpt_worker, args=(_write,), daemon=True)
                self._ckpt_thread.start()
                return
            save_pytree({"params": params, "mod_state": mod_state,
                         "driver": drv}, target, layout=layout)
            save_pytree(opt_state, state_target, layout=layout)
        self._gc_ckpts()
        logger.info("Checkpoint written at iteration %d to %s", n,
                    self._ckpt_path)

    def _gc_ckpts(self):
        """keep-last-k snapshot GC (set_checkpoint keep_last) — the
        newest checksum-valid pair survives unconditionally."""
        k = getattr(self, "_ckpt_keep_last", None)
        if k:
            from bigdl_tpu.utils.file import gc_checkpoints
            gc_checkpoints(self._ckpt_path, k)

    def _ckpt_worker(self, write_fn):
        try:
            write_fn()
        except BaseException as e:  # surfaced at the next join
            self._ckpt_error = e

    def _join_ckpt_writer(self):
        t = getattr(self, "_ckpt_thread", None)
        if t is not None:
            t.join()
            self._ckpt_thread = None
        err = getattr(self, "_ckpt_error", None)
        if err is not None:
            self._ckpt_error = None
            raise RuntimeError("async checkpoint write failed") from err
