"""Synthetic-data training throughput harness (reference
models/utils/DistriOptimizerPerf.scala:35-150 / LocalOptimizerPerf.scala —
constant/random synthetic input, models inception/vgg/resnet, reports the
canonical records/second; extended with MFU, which the reference lacks but
the BASELINE north-star requires).

    python -m bigdl_tpu.cli.perf -m resnet50 -b 128 -i 20 --dataType constant
"""

from __future__ import annotations

import argparse
import json
import os
import time


# bf16 peak per chip (public figures); the MFU denominator. Matched by
# substring against the *squashed* (space-stripped, lowered) device_kind,
# most specific first, so real-world kinds like "TPU v5 lite" (v5e), "TPU
# v5p slice", "TPU v4 lite" all resolve. There is no default row: an
# accelerator missing from the table is an error (a nominal peak once
# inflated a reported MFU ~197x), and the CPU test platform has no MFU.
_PEAK_FLOPS = (
    ("v6lite", 918e12), ("v6e", 918e12), ("trillium", 918e12),
    ("v5lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4lite", 138e12), ("v4", 275e12),
    ("v3", 123e12), ("v2", 46e12),
)


def _peak_flops(device):
    """Return (peak_bf16_flops, matched_label) for one chip;
    ``(None, "cpu")`` on the explicit CPU test platform (MFU is then
    null). An accelerator whose kind is not in the table raises."""
    if device.platform == "cpu":
        return None, "cpu"
    kind = device.device_kind
    squashed = kind.replace(" ", "").replace("-", "").lower()
    for k, v in _PEAK_FLOPS:
        if k in squashed:
            return v, k
    raise ValueError(
        f"device_kind {kind!r} ({device.platform}) is not in the bf16 "
        f"peak table (cli/perf.py _PEAK_FLOPS) — add its published peak "
        f"rather than report an MFU against a made-up one")


def mosaic_kernels(compiled) -> list:
    """Names of the Pallas kernels a compiled program runs through Mosaic
    (its ``tpu_custom_call`` custom calls), sorted and deduplicated. Empty
    off-TPU, where Pallas kernels run interpreted as plain HLO — so a
    non-empty list is proof that neither interpret mode nor a dense
    fallback stood in for the kernel."""
    import re

    names = set()
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" not in line:
            continue
        # op_name="jit(step)/.../transpose(jvp(flash_dq))/pallas_call":
        # the pallas_call's name= under whatever transforms wrapped it
        m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        names.add(m.group(1) if m else "unnamed")
    return sorted(names)


_LM_VOCAB = 32000  # shared by the model head and the synthetic token data


def _bn_subset(m, k: int = 32):
    from bigdl_tpu.nn import set_bn_stat_sample
    return set_bn_stat_sample(m, k)


def _bn_fused(m, mode=True):
    from bigdl_tpu.nn import set_bn_fused
    return set_bn_fused(m, mode)


# build_model(seq_len=..., lm_attn_impl=...) installs overrides here for
# the duration of one table call — tpulint builds LMs with the flash
# kernel forced on (TPU-projected trace off-chip) and a custom seq
_LM_OVERRIDE: dict = {}


def _lm(*, num_kv_heads=2, pos_encoding="rope", **kw):
    """Shared LM-config plumbing for the perf model zoo (vocab + the
    backend-conditional flash selection live in ONE place)."""
    import jax

    from bigdl_tpu import models

    kw = dict(kw)
    kw.setdefault("attn_impl",
                  "flash" if jax.default_backend() == "tpu" else None)
    kw.update(_LM_OVERRIDE)
    return models.transformer_lm(
        _LM_VOCAB, pos_encoding=pos_encoding, num_kv_heads=num_kv_heads,
        **kw)


def build_model(name: str, class_num: int = 1000, seq_len=None,
                lm_attn_impl=None):
    import jax

    from bigdl_tpu import models

    table = {
        "inception_v1": lambda: models.inception_v1_no_aux(class_num),
        "inception_v2": lambda: models.inception_v2(class_num),
        "vgg16": lambda: models.vgg16(class_num),
        "vgg19": lambda: models.vgg19(class_num),
        "alexnet": lambda: models.alexnet(class_num),
        "resnet50": lambda: models.resnet50(class_num),
        "resnet50_s2d": lambda: models.resnet50(class_num, s2d_stem=True),
        # BN stats from 32 batch rows: cuts the stats-pass HBM re-read of
        # every activation (the dominant BN cost, PERF.md §2) by b/32
        "resnet50_bnss": lambda: _bn_subset(models.resnet50(class_num)),
        # single-read Pallas BN stats (ops/bn_kernel.py): the stats pass
        # is the #1 sync op category (PERF.md §2); exact semantics,
        # unlike the bnss subset sampling. Measured −46% on chip (§8.2)
        # — kept as the A/B middle leg against _fba below
        "resnet50_fbn": lambda: _bn_fused(models.resnet50(class_num)),
        # the FULL fused BN block (ISSUE 2): stats+apply+absorbed-ReLU
        # one kernel forward, Σdy/Σ(dy·x̂)+dx one kernel backward —
        # attacks the 34 ms backward (PERF.md §10) where the stats-only
        # kernel above LOST 46% by unfusing its elementwise neighbors
        "resnet50_fba": lambda: _bn_fused(models.resnet50(class_num),
                                          "apply"),
        # CIFAR-shaped depth-20 resnet (reference models/resnet/README
        # recipe) — the fast time-to-accuracy config
        "resnet20_cifar": lambda: models.resnet_cifar(
            20, class_num if class_num != 1000 else 10),
        "lenet5": lambda: models.lenet5(10),
        # beyond-reference vision family: patchify conv (3*16*16 = 768
        # contraction vs the resnet stem's MXU-starved 3-channel 7x7),
        # 128-wide heads, flash on TPU — see models/vit.py
        "vit_b16": lambda: models.vit_b16(
            class_num, attn_impl=("flash" if jax.default_backend() ==
                                  "tpu" else None)),
        "vit_s16": lambda: models.vit_s16(
            class_num, attn_impl=("flash" if jax.default_backend() ==
                                  "tpu" else None)),
        # causal LMs, 32k vocab. _lm fills the shared plumbing: the
        # Pallas flash kernel only off-interpret on TPU; elsewhere the
        # dense path keeps CPU benchmark runs fast.
        "transformer_lm": lambda: _lm(
            d_model=512, num_layers=8, num_heads=8, max_len=512,
            pos_encoding="sinusoidal", num_kv_heads=None),
        # modern-config A/B: RoPE + grouped-query (2 kv heads)
        "transformer_lm_rope": lambda: _lm(
            d_model=512, num_layers=8, num_heads=8, max_len=512),
        # larger config at 1k context: matmuls big enough that MFU
        # reflects the MXU, not dispatch/embedding overhead
        "transformer_lm_1k": lambda: _lm(
            d_model=1024, num_layers=12, num_heads=16, max_len=1024,
            num_kv_heads=4),
        # head-dim A/B: same d_model/layers/FLOPs, 8 heads of 128 instead
        # of 16 of 64 — the MXU contracts over the head dim in both
        # attention matmuls, and 64 lanes half-fills its 128-wide tiles.
        # Measured +24% tok/s on chip at 512-wide flash blocks; 53.7%
        # MFU — past the 50% north star (PERF.md §8.2).
        "transformer_lm_1k_hd128": lambda: _lm(
            d_model=1024, num_layers=12, num_heads=8, max_len=1024),
        # long-context flagship: 16k tokens END-TO-END through the
        # training step on one chip — flash-only territory (dense
        # attention needs a 17 GB score matrix from seq 8k up and
        # OOM-fails, PERF.md §8.2); remat='dots' keeps the MXU outputs
        # resident and recomputes the bandwidth-bound intermediates
        "transformer_lm_16k": lambda: _lm(
            d_model=1024, num_layers=12, num_heads=8, max_len=16384,
            remat="dots"),
        # 32k: double the 16k flagship — the flash kernel is
        # compiled-verified at this length (flash_bench; dense needs a
        # 68 GB score matrix), full-recompute remat for the activations
        "transformer_lm_32k": lambda: _lm(
            d_model=1024, num_layers=12, num_heads=8, max_len=32768,
            remat="full"),
        # decoder-decoder LMs (models/sambay_lm.py): Mamba + window
        # attention, then gated memory units + cross-attention over one
        # shared KV cache. The first is the class at smoke-test sizes, as
        # transformer_lm is of its class; the second has
        # Phi-4-mini-flash-reasoning's published sizes (3.85B parameters:
        # serve it with --bf16, and --buckets 1 --seq 256 for /predict)
        "sambay_lm": lambda: _sambay(
            _LM_VOCAB, d_model=256, num_layers=8, num_heads=4,
            num_kv_heads=2, d_ff=512, window=64, max_len=512),
        "phi4_mini_flash": lambda: _sambay(
            200064, d_model=2560, num_layers=32, num_heads=40,
            num_kv_heads=20, d_ff=10240, window=512, max_len=4096),
        # linear-attention / softmax hybrids with a routed FFN in every
        # layer (models/hybrid_moe_lm.py): three KDA layers after each
        # gated NoPE GQA layer, sigmoid top-k routing, a shared expert.
        # The first is the class at smoke-test sizes, all 16 experts held;
        # the second is Solar-Open2-250B's published widths as one chip's
        # share of an eight-way expert-parallel deployment: 4 of 48
        # layers, 40 of 320 experts a layer, 24,576 of 196,608 vocabulary
        # rows (3.31B parameters: serve it with --bf16)
        "hybrid_moe_lm": lambda: _hybrid_moe(
            "hybrid_moe_lm", _LM_VOCAB, d_model=256, num_layers=4,
            num_heads=4, num_kv_heads=2, head_dim=64, gate_rank=32,
            num_experts=16, top_k=4, expert_width=128, max_len=512),
        "solar_open2": lambda: _hybrid_moe("solar_open2", max_len=4096),
        # the same class under its second configuration: plain GQA by two
        # layout lists (a NoPE full-attention layer, then three RoPE
        # layers with a 4,096-row window ring), an early softmax-top-k
        # router over 64 ReGLU experts, all held. SmallThinker-21BA3B's
        # published widths, 8 of 52 layers (3.97B parameters: serve it
        # with --bf16)
        "smallthinker": lambda: _hybrid_moe("smallthinker", max_len=16384),
    }
    if name not in table:
        raise SystemExit(f"unknown model {name}; choose from {list(table)}")
    size = {"lenet5": (28, 28, 1),
            "resnet20_cifar": (32, 32, 3),
            "transformer_lm": (512,),
            "transformer_lm_rope": (512,),
            "transformer_lm_1k": (1024,),
            "transformer_lm_1k_hd128": (1024,),
            "transformer_lm_16k": (16384,),
            "transformer_lm_32k": (32768,),
            "sambay_lm": (seq_len or 512,),
            "phi4_mini_flash": (seq_len or 4096,),
            "hybrid_moe_lm": (seq_len or 512,),
            "solar_open2": (seq_len or 4096,),
            "smallthinker": (seq_len or 16384,)}.get(name, (224, 224, 3))
    # LM build overrides (tpulint): forced attn_impl and/or seq length
    # apply only to transformer_lm* names and only for this one call
    global _LM_OVERRIDE
    over = {}
    if name.startswith("transformer_lm"):
        if lm_attn_impl is not None:
            over["attn_impl"] = lm_attn_impl
        if seq_len is not None:
            over["max_len"] = int(seq_len)
            size = (int(seq_len),)
    prev = _LM_OVERRIDE
    _LM_OVERRIDE = over
    try:
        model = table[name]()
    finally:
        _LM_OVERRIDE = prev
    return model, size


def _sambay(vocab, **kw):
    import jax

    from bigdl_tpu import models

    return models.sambay_lm(
        vocab, attn_impl="flash" if jax.default_backend() == "tpu" else None,
        **kw)


def _hybrid_moe(preset, *a, **kw):
    import jax

    from bigdl_tpu import models

    return getattr(models, preset)(
        *a, attn_impl="flash" if jax.default_backend() == "tpu" else None,
        **kw)


def _short_side(crop) -> int:
    """The resize target feeding a random crop: the standard 256-for-224
    headroom ratio, generalized so non-224 image models (resnet20_cifar)
    can train from record shards too."""
    if tuple(crop) == (224, 224):
        return 256
    return max(8, (max(crop) * 8) // 7)


def _record_batches(source: str, batch: int, n_threads: int = 0,
                    crop=(224, 224)):
    """Endless MiniBatch iterator over ``record:<shard-dir>`` — the
    train-from-storage bench path (decode + per-sample augment + batch +
    host->device all inside the timed loop; round-2 weak #2: the synthetic
    bench can't see an input-bound regime)."""
    import os

    from bigdl_tpu.dataset.streaming import RecordImageDataSet

    ds = RecordImageDataSet(
        source, batch_size=batch, crop=crop, train=True,
        short_side=_short_side(crop),
        mean=[123.68, 116.779, 103.939],
        std=[58.4, 57.1, 57.4],
        n_threads=n_threads or min(32, (os.cpu_count() or 4) * 2),
        window=4)
    while True:
        for mb in ds:
            yield mb


def _executor_record_batches(source: str, batch: int, workers: int,
                             depth: int = 2, stage: str = "off",
                             strategy=None, crop=(224, 224)):
    """Endless executor-fed record feed (ISSUE 13): the SAME decode/
    augment recipe as :func:`_record_batches` (so A/B rows compare the
    feed machinery, not the pipeline params), driven by the
    ``dataset/pipeline/`` executor + optional host->device staging.
    Returns ``(iterator, provenance dict)``."""
    from bigdl_tpu.dataset.pipeline import (EpochPlan, ExecutorDataSet,
                                            StagedDataSet,
                                            StreamingSampleSource)
    from bigdl_tpu.dataset.streaming import RecordImageDataSet

    # worker parallelism lives in the executor now — the inner dataset
    # only contributes its per-sample decode path (_load_sample)
    rds = RecordImageDataSet(
        source, batch_size=batch, crop=crop, train=True,
        short_side=_short_side(crop), mean=[123.68, 116.779, 103.939],
        std=[58.4, 57.1, 57.4], n_threads=1, window=1)
    src = StreamingSampleSource(rds)
    plan = EpochPlan(len(src), batch, seed=rds.seed, shuffle=True,
                     process_index=0, process_count=1)
    ds = ExecutorDataSet(src, workers=workers, depth=depth, plan=plan)
    prov_ds = ds
    if stage != "off":
        ds = StagedDataSet(ds, stage=stage, depth=depth, strategy=strategy)
        prov_ds = ds

    def endless():
        while True:
            for mb in ds:
                yield mb
            ds.shuffle()  # advance the plan epoch (legacy feed parity)

    return endless(), prov_ds.signature()


def _annotate_conv_layouts(out: dict) -> None:
    """Stamp the active non-default conv layout policy — global triple
    AND installed per-geometry decisions — into a result dict; shared by
    run() and run_time_to_acc() so their JSON provenance cannot drift
    apart. Delegates to the shared assembly (ISSUE 18 satellite) —
    perf JSON, /metrics _info, bench companions, and batch-predict all
    read the same code now."""
    from bigdl_tpu.cli.provenance import provenance_dict
    core = provenance_dict()
    for k in ("conv_layouts", "conv_geom"):
        if k in core:
            out[k] = core[k]


def _annotate_autotune(out: dict) -> None:
    """Stamp the run's tuning provenance (mode + per-key decision or
    'default') into a result dict — ISSUE 1 acceptance: every perf JSON
    line says which decisions it ran under."""
    from bigdl_tpu.cli.provenance import provenance_dict
    core = provenance_dict()
    if "autotune" in core:
        out["autotune"] = core["autotune"]


def _annotate_bn_fused(out: dict, model) -> None:
    """Stamp the model's effective BN fusion mode (off/stats/apply) the
    same way the autotune decisions are stamped, so fused-vs-stats-vs-
    default A/B rows are self-describing (ISSUE 2 satellite)."""
    from bigdl_tpu.cli.provenance import provenance_dict
    out["bn_fused"] = provenance_dict(model)["bn_fused"]


_PHASE_COLUMNS = ("data_wait_s", "h2d_s", "dispatch_s", "device_s",
                  "ckpt_s", "stall_frac")
# ISSUE 12: the memory columns, schema-stable like the phase columns —
# null obs-off; under --obs the peak HBM bytes (live device.memory_stats
# when the backend has them, else the static plan's modeled total), the
# headroom fraction against the matched per-chip capacity, and the
# compact per-category plan as the `mem` detail dict.
_MEM_COLUMNS = ("hbm_peak_bytes", "hbm_headroom_frac", "mem")


def _annotate_obs_phases(out: dict, obs_state, phase: dict | None = None,
                         wall_s: float | None = None) -> None:
    """Stamp the step-phase columns into a result dict (ISSUE 7). The
    columns are ALWAYS present so the JSON schema is stable: null in an
    obs-off run (whose output stays byte-identical to pre-obs output
    modulo exactly these nulls), measured cumulative seconds under
    --obs. ``stall_frac`` is the feed-stall fraction of wall time — the
    number PERF.md §4 could previously only infer. Under --obs the
    trace/capture artifacts ride along as ``obs``: a capture's record
    says where its ``jax.profiler`` directory is and whether it parsed
    (read it in XProf/Perfetto; the ledger's numbers come from
    ``benchmark/lib/trace.py``)."""
    for c in _MEM_COLUMNS:
        out[c] = None
    on = (obs_state is not None and obs_state.enabled
          and phase is not None)
    if not on:
        for c in _PHASE_COLUMNS:
            out[c] = None
        return
    out["data_wait_s"] = round(phase.get("data_wait", 0.0), 4)
    out["h2d_s"] = round(phase.get("h2d", 0.0), 4)
    out["dispatch_s"] = round(phase.get("dispatch", 0.0), 4)
    out["device_s"] = round(phase.get("device", 0.0), 4)
    out["ckpt_s"] = round(phase.get("ckpt", 0.0), 4)
    out["stall_frac"] = (round(phase.get("data_wait", 0.0) / wall_s, 4)
                         if wall_s else None)
    plan = getattr(obs_state, "mem_plan", None)
    sampler = getattr(obs_state, "mem_sampler", None)
    if plan is not None:
        from bigdl_tpu.obs import memory as _mem
        live_peak = (sampler.peak_bytes if sampler is not None else None)
        peak = live_peak or plan["total_bytes"]
        cap = plan["hbm_bytes"]
        out["hbm_peak_bytes"] = int(peak)
        out["hbm_headroom_frac"] = (round((cap - peak) / cap, 4)
                                    if cap else None)
        m = _mem.compact(plan)
        m["source"] = "live" if live_peak else "plan"
        live = (sampler.annotation() if sampler is not None else None)
        if live:
            m["live"] = live
        out["mem"] = m
    info = obs_state.finalize()
    o: dict = {}
    if "trace_json" in info:
        o["trace_json"] = info["trace_json"]
        o["span_events"] = info["span_events"]
    if "metrics_port" in info:  # the bound (or auto-picked) listener
        o["metrics_port"] = info["metrics_port"]
        o["metrics_url"] = info["metrics_url"]
    if "captures" in info:
        o["captures"] = [
            {k: c[k] for k in ("start_step", "stop_step", "trigger",
                               "ok", "dir", "error") if k in c}
            for c in info["captures"]]
    if o:
        out["obs"] = o


def _annotate_supervisor(out: dict, supervisor) -> None:
    """Stamp the structured fault/recovery log next to bn_fused/lint
    (ISSUE 6): under --supervise the full supervisor annotation
    (attempts/retries/events incl. injected faults); with only a
    --faultPlan active, the raw injected-fault events — either way a
    perf row produced under faults says so."""
    if supervisor is not None:
        out["supervisor"] = supervisor.annotation()
        return
    from bigdl_tpu.resilience.faults import injected_events
    ev = injected_events()
    if ev:
        out["faults"] = ev


# (d_model, layers, heads, seq) of the LM zoo configs — the pp/ep
# harness builders below size their pipeline stack / MoE block from the
# requested model name so an A/B against the dp/tp/sp legs compares the
# same transformer geometry
_LM_GEOM = {
    "transformer_lm": (512, 8, 8, 512),
    "transformer_lm_rope": (512, 8, 8, 512),
    "transformer_lm_1k": (1024, 12, 16, 1024),
    "transformer_lm_1k_hd128": (1024, 12, 8, 1024),
    "transformer_lm_16k": (1024, 12, 8, 16384),
    "transformer_lm_32k": (1024, 12, 8, 32768),
}


def _setup_strategy_harness(strat_name: str, model_name: str, batch: int,
                            mesh, mesh_axes: dict, dtype,
                            seq_len: int | None):
    """Build the pp/ep timed-loop pieces (ISSUE 8). These strategies
    compose with the STEP structure, not just parameter placement — a
    GPipe pipeline schedules microbatches through ppermute hops, an
    expert-parallel MoE routes tokens — so they get dedicated builders
    that return a step with the harness's uniform
    ``(params, mod_state, opt_state, x, y, rng) -> 4-tuple`` signature.
    Geometry comes from the requested transformer_lm* config
    (:data:`_LM_GEOM`, seq overridable via --seq); the criterion is MSE
    over the block stack (embedding/head run replicated outside a real
    pipeline and are excluded, exactly like ``__graft_entry__.py``'s
    dryrun)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD

    geom = _LM_GEOM.get(model_name)
    if geom is None:
        raise SystemExit(
            f"--strategy {strat_name} sizes its transformer stack from "
            f"the model name; choose one of {sorted(_LM_GEOM)}")
    d_model, layers, heads, seq = geom
    if seq_len is not None:
        seq = int(seq_len)
    crit = nn.MSECriterion()
    opt = SGD(learning_rate=0.01, momentum=0.9)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, seq, d_model), dtype)
    y = jnp.asarray(rs.randn(batch, seq, d_model), dtype)

    if strat_name == "pp":
        from bigdl_tpu.parallel import (PipelineStack,
                                        make_pipeline_train_step,
                                        place_pipeline_params)

        stages = mesh_axes["pipe"]
        if layers % stages:
            raise SystemExit(
                f"--strategy pp: {layers} layers must divide over "
                f"{stages} pipeline stages (try pp:{layers // 2} or a "
                "deeper model)")
        micro = stages  # GPipe bubble (P-1)/(M+P-1); M=P keeps it <50%
        data_ax = mesh_axes.get("data", 1)
        if batch % micro or (batch // micro) % data_ax:
            raise SystemExit(
                f"--strategy pp: batch {batch} must split into {micro} "
                f"microbatches of a multiple of the data axis "
                f"({data_ax})")
        stack = PipelineStack(
            nn.TransformerEncoderLayer(d_model=d_model, num_heads=heads,
                                       d_ff=4 * d_model), layers)
        params = place_pipeline_params(mesh,
                                       stack.init(jax.random.PRNGKey(0)),
                                       "pipe")
        opt_state = opt.init(jax.device_get(params))
        compile_for = make_pipeline_train_step(
            stack, mesh, crit, opt, microbatches=micro, axis="pipe",
            data_axis="data")
        raw = compile_for(opt_state, params)

        def step(params, mod_state, opt_state, x, y, rng):
            p, o, loss = raw(params, opt_state, x, y, rng)
            return p, mod_state, o, loss

        return {"step": step, "single_step": step, "params": params,
                "opt_state": opt_state, "x": x, "y": y,
                "in_shape": (seq, d_model)}

    # ep: expert-parallel MoE — experts sharded over the expert axis,
    # the top-2 router's dispatch/combine einsums become the measured
    # all-to-all-shaped traffic
    from bigdl_tpu.core import Sequential

    n_exp = mesh_axes["expert"]
    moe = nn.MoE(Sequential(nn.Linear(d_model, 2 * d_model), nn.ReLU(),
                            nn.Linear(2 * d_model, d_model)),
                 num_experts=n_exp, d_model=d_model, top_k=2,
                 capacity_factor=2.0)
    params = moe.place_expert_parallel(mesh,
                                       moe.init(jax.random.PRNGKey(0)))
    opt_state = opt.init(params)

    def train_step(params, mod_state, opt_state, x, y, rng):
        def loss_fn(p):
            out, st = moe.apply(p, moe.init_state(), x, training=True)
            return (crit(out.astype(jnp.float32),
                         y.astype(jnp.float32))
                    + 0.01 * st["aux_loss"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_o = opt.update(grads, opt_state, params)
        return new_p, mod_state, new_o, loss

    step = jax.jit(train_step, donate_argnums=(0, 2))
    return {"step": step, "single_step": train_step, "params": params,
            "opt_state": opt_state, "x": x, "y": y,
            "in_shape": (seq, d_model)}


def run(model_name: str, batch: int, iterations: int, data_type: str,
        use_bf16: bool = True, data_parallel: bool = False,
        data_source: str | None = None, inner_steps: int = 1,
        profile_dir: str | None = None, autotune: str | None = None,
        fused_bn: str | None = None, lint: dict | None = None,
        supervisor=None, obs_state=None, strategy: str | None = None,
        seq_len: int | None = None, grad_compress: str | None = None,
        grad_buckets: str | None = None, elastic=None,
        data_workers: int = 0, prefetch_depth: int = 2,
        stage: str = "off"):
    """Throughput harness entry. ``autotune`` optionally installs the
    tuning mode (the CLI does it via --autotune/apply_platform; library
    callers pass it directly). ``fused_bn`` ('off'/'stats'/'apply')
    installs the Pallas BN path on the built model — the flag spelling of
    the resnet50_fbn/_fba model names. ``strategy`` ('dp'/'tp'/'sp'/
    'pp'/'ep', optionally NAME:K) runs the timed loop over every visible
    device via the ``parallel/`` API (ISSUE 8); ``data_parallel`` is the
    deprecated alias for 'dp'. ``grad_compress``/``grad_buckets`` are the
    --gradCompress/--gradBuckets pair (ISSUE 10): bucketed 16-bit
    gradient all-reduce under a multi-device dp/tp strategy. The conv
    layout policy is snapshotted and restored so back-to-back runs in
    one process stay independent (ADVICE r5 #1)."""
    from bigdl_tpu import tuning
    from bigdl_tpu.ops import conv2d

    if autotune is not None:
        tuning.set_mode(autotune)
    tuning.reset_decisions()
    snap = conv2d.policy_snapshot()
    try:
        return _run_timed(model_name, batch, iterations, data_type,
                          use_bf16=use_bf16, data_parallel=data_parallel,
                          data_source=data_source, inner_steps=inner_steps,
                          profile_dir=profile_dir, fused_bn=fused_bn,
                          lint=lint, supervisor=supervisor,
                          obs_state=obs_state, strategy=strategy,
                          seq_len=seq_len, grad_compress=grad_compress,
                          grad_buckets=grad_buckets, elastic=elastic,
                          data_workers=data_workers,
                          prefetch_depth=prefetch_depth, stage=stage)
    finally:
        conv2d.restore_policy(snap)


def _run_timed(model_name: str, batch: int, iterations: int, data_type: str,
               use_bf16: bool = True, data_parallel: bool = False,
               data_source: str | None = None, inner_steps: int = 1,
               profile_dir: str | None = None,
               fused_bn: str | None = None, lint: dict | None = None,
               supervisor=None, obs_state=None,
               strategy: str | None = None, seq_len: int | None = None,
               grad_compress: str | None = None,
               grad_buckets: str | None = None, elastic=None,
               data_workers: int = 0, prefetch_depth: int = 2,
               stage: str = "off"):
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    # elastic attempt wall-clock starts here: on a post-loss retry the
    # mesh re-formation + rebuild + recompile up to warmup IS restore_ms
    t_attempt0 = time.perf_counter()

    # persistent compile cache: library callers (the chip smoke, the
    # tests) reach the harness without going through apply_platform
    from bigdl_tpu.cli import common as _common
    _common.enable_compile_cache()

    # ----- strategy resolution (ISSUE 8): the hidden data_parallel-only
    # branch is gone — all five MULTICHIP-validated families resolve
    # through the shared cli/common machinery (mesh shapes, the
    # innerSteps x strategy SystemExit contract), with --dataParallel
    # kept as a deprecated alias for dp that still degrades silently on
    # one device (its historical behavior)
    strat_spec = strategy if strategy is not None else (
        "dp" if data_parallel else None)
    strat_name, strat_k = _common.parse_strategy_spec(strat_spec)
    mesh = None
    mesh_axes = None
    elastic_devices = None
    if elastic is not None:
        if strat_name != "dp":
            raise SystemExit(
                "--elastic composes with --strategy dp only (elastic "
                "reshape is a data-parallel contract)")
        # the surviving-device roster for THIS attempt; below
        # --minDevices this raises SupervisorGaveUp (clean give-up,
        # never a retry)
        elastic_devices = elastic.probe()
    if strat_name is not None:
        n_all = (len(elastic_devices) if elastic_devices is not None
                 else len(jax.devices()))
        if n_all <= 1:
            if strategy is not None:
                raise SystemExit(
                    f"--strategy {strat_name} needs more than one "
                    "device; off-chip set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8 (the "
                    "MULTICHIP dryrun recipe)")
            strat_name = None  # deprecated alias: historical no-op
        else:
            _common.check_strategy_dispatch(inner_steps, "--innerSteps")
            if (strat_name == "sp"
                    and not model_name.startswith("transformer_lm")):
                raise SystemExit(
                    "--strategy sp shards the sequence axis via ring "
                    "attention; it needs a transformer_lm* model")
            mesh_axes = _common.strategy_mesh_axes(strat_name, n_all,
                                                   strat_k)
            from bigdl_tpu.parallel import make_mesh
            mesh = make_mesh(mesh_axes, elastic_devices)
            data_ax = mesh_axes.get("data", 1)
            if batch % data_ax and elastic is None:
                # elastic runs pad/trim to divisibility instead
                # (ElasticDataParallel.shard_batch, --elastic policy)
                raise SystemExit(
                    f"batch {batch} must be divisible by the data axis "
                    f"({data_ax}) of --strategy {strat_name} "
                    f"(mesh {mesh_axes})")

    # ----- gradient-communication config (ISSUE 10): bucketed 16-bit
    # all-reduce through DataParallel.reduce_grads — so it composes with
    # the strategies that route grads there (dp/tp/sp); pp/ep build
    # their own step structure and refuse cleanly rather than silently
    # running uncompressed
    from bigdl_tpu.parallel.grad_comm import make_config as _mk_grad_comm
    try:
        grad_comm_cfg = _mk_grad_comm(grad_compress, grad_buckets)
    except ValueError as e:
        raise SystemExit(str(e))
    if grad_comm_cfg is not None and grad_comm_cfg.active:
        if strat_name is None:
            raise SystemExit(
                "--gradCompress compresses the cross-device gradient "
                "all-reduce; it needs a multi-device --strategy (dp/tp)")
        if strat_name in ("pp", "ep"):
            raise SystemExit(
                f"--gradCompress rides DataParallel.reduce_grads; "
                f"--strategy {strat_name} builds its own step structure "
                "and has no replicated-grad all-reduce to compress")

    # conv-layout decision for this device AND run configuration. The
    # window-2 combination matrix (PERF.md §8.2) measured the shipped
    # decision POSITIVE alone (+1.1%) but NEGATIVE chained with
    # inner-stepping (2,630 vs 2,678 img/s) or the s2d stem (2,579 vs
    # 2,674) — so those configurations resolve their own autotune keys
    # (default all-NHWC until measured) instead of skipping installation
    # and inheriting whatever an earlier run left behind. inner_steps is
    # normalized to 1 further down for data_source runs — mirror that
    # here so those (plain-dispatch) runs still get the decision
    _eff_inner = (1 if (data_source is not None or strat_name is not None)
                  else inner_steps)
    from bigdl_tpu import tuning
    tuning.install_conv_layouts(
        "s2d" if model_name.endswith("_s2d")
        else ("inner" if _eff_inner > 1 else "plain"))

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if (use_bf16 and on_tpu) else jnp.float32

    if strat_name in ("pp", "ep"):
        # pipeline/expert parallelism compose with the STEP structure,
        # not just the placement — dedicated harness builders below
        setup = _setup_strategy_harness(strat_name, model_name, batch,
                                        mesh, mesh_axes, dtype, seq_len)
        model, in_shape, is_lm = None, setup["in_shape"], False
        params, mod_state, opt_state = (setup["params"], {},
                                        setup["opt_state"])
        x, y = setup["x"], setup["y"]
        step, single_step = setup["step"], setup["single_step"]
        strat = None
    else:
        lm_attn = None
        if strat_name == "sp":
            if not model_name.startswith("transformer_lm"):
                raise SystemExit(
                    "--strategy sp shards the sequence axis via ring "
                    "attention; it needs a transformer_lm* model")
            from bigdl_tpu.parallel import make_ring_attention
            lm_attn = make_ring_attention(mesh, "seq", batch_axis="data")

        model, in_shape = build_model(model_name, seq_len=seq_len,
                                      lm_attn_impl=lm_attn)
        _common.apply_fused_bn(model, fused_bn)
        is_lm = model_name.startswith("transformer_lm")
        if strat_name == "sp" and in_shape[0] % mesh_axes["seq"]:
            raise SystemExit(
                f"--strategy sp: sequence length {in_shape[0]} must be "
                f"divisible by the seq axis ({mesh_axes['seq']}); "
                "shrink/resize with --seq")
        crit = (nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
                if is_lm else nn.ClassNLLCriterion())
        opt = SGD(learning_rate=0.01, momentum=0.9)

        rng = np.random.RandomState(0)
        if is_lm:  # token ids in, per-token targets
            if dtype == jnp.bfloat16:
                model.compute_dtype = dtype  # cast lives after the
                # embedding
            x_host = rng.randint(0, _LM_VOCAB,
                                 (batch, *in_shape)).astype(np.int32)
            y_host = rng.randint(0, _LM_VOCAB,
                                 (batch, *in_shape)).astype(np.int32)
        elif data_type == "constant":
            x_host = np.ones((batch, *in_shape), np.float32)
            y_host = rng.randint(0, 1000 if in_shape[0] > 30 else 10,
                                 batch).astype(np.int32)
        else:
            x_host = rng.randn(batch, *in_shape).astype(np.float32)
            y_host = rng.randint(0, 1000 if in_shape[0] > 30 else 10,
                                 batch).astype(np.int32)

        params = model.init(jax.random.PRNGKey(0))
        mod_state = model.init_state()
        opt_state = opt.init(params)

        strat = None
        if strat_name == "dp" and elastic is not None:
            # elastic dp: batch placement pads (hold) or trims (scale)
            # to the surviving data-axis size; everything else is plain
            # DataParallel, so at full topology this is bit-identical
            from bigdl_tpu.resilience.elastic import ElasticDataParallel

            strat = ElasticDataParallel(mesh,
                                        batch_policy=elastic.batch_policy,
                                        grad_comm=grad_comm_cfg)
        elif strat_name == "dp" or strat_name == "sp":
            from bigdl_tpu.parallel import DataParallel

            strat = DataParallel(mesh, grad_comm=grad_comm_cfg)
        elif strat_name == "tp":
            from bigdl_tpu.parallel import TensorParallel

            strat = TensorParallel(mesh, model)
            strat.grad_comm = grad_comm_cfg
        if strat is not None:
            params, mod_state, opt_state = strat.place(
                params, mod_state, opt_state)

        def train_step(params, mod_state, opt_state, x, y, rng):
            def loss_fn(p):
                xc = x.astype(dtype) if jnp.issubdtype(x.dtype,
                                                       jnp.floating) else x
                out, ms = model.apply(p, mod_state, xc, training=True,
                                      rng=rng)
                return crit(out.astype(jnp.float32), y), ms

            (loss, ms), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if strat is not None:
                grads, loss = strat.reduce_grads(grads, loss)
            new_p, new_o = opt.update(grads, opt_state, params)
            return new_p, ms, new_o, loss

        single_step = train_step  # FLOPs are counted per single step

        if strat is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if strat_name == "sp":
                # token ids sharded batch x seq so ring attention's
                # shard_map sees its home layout without a reshard
                spec = P("data", "seq")
                step = strat.compile_step(train_step, batch_spec=spec)
                sh = NamedSharding(mesh, spec)
                x = jax.device_put(jnp.asarray(x_host), sh)
                y = jax.device_put(jnp.asarray(y_host), sh)
            else:
                step = strat.compile_step(train_step)
                x, y = strat.shard_batch(x_host, y_host)
            inner_steps = 1
        else:
            if data_source is not None:
                inner_steps = 1  # fresh host batch every step by
                # definition
            if inner_steps > 1:
                # amortize per-dispatch host overhead by chaining steps
                # inside one program; same resident batch, per-step
                # folded rng
                # — the pure-compute meter the reference's
                # LocalOptimizerPerf is
                def train_step(params, mod_state, opt_state, x, y, rng):  # noqa: F811
                    def body(i, c):
                        p, ms, o, _ = c
                        return single_step(p, ms, o, x, y,
                                           jax.random.fold_in(rng, i))
                    init = (params, mod_state, opt_state,
                            jnp.zeros((), jnp.float32))
                    return jax.lax.fori_loop(0, inner_steps, body, init)

            step = jax.jit(train_step, donate_argnums=(0, 1, 2))
            x, y = jnp.asarray(x_host), jnp.asarray(y_host)

    k = jax.random.PRNGKey(1)
    # Two independent FLOPs estimates for the MFU numerator:
    #  * analytic — walk the train-step jaxpr and sum 2*MAC for every
    #    dot_general / conv (utils/flops.py); auditable, backend-free;
    #  * HLO — compiled.cost_analysis()["flops"], XLA's own count.
    # MFU is reported from the analytic number; both appear in the JSON
    # and a >2x disagreement is flagged rather than silently trusted.
    flops_analytic, flops_error = 0.0, None
    flops_kinds = {"matmul": 0.0, "conv": 0.0}
    try:
        from bigdl_tpu.utils.flops import fn_flops_by_kind

        flops_kinds = fn_flops_by_kind(single_step, params, mod_state,
                                       opt_state, x, y, k)
        flops_analytic = flops_kinds["matmul"] + flops_kinds["conv"]
    except Exception as e:  # record, never hide — the basis field (below)
        flops_error = f"{type(e).__name__}: {e}"[:200]
    flops_hlo = 0.0
    n_dev = (int(np.prod(list(mesh_axes.values())))
             if mesh_axes is not None else 1)
    compiled = None
    if strat_name != "pp":  # pp's step is a Python adapter over its jit
        # AOT-compile the exact step the loop runs; a compiler refusal (a
        # Mosaic lowering error, an over-budget VMEM request) surfaces
        # here instead of resurfacing later through a second, lazy compile
        compiled = step.lower(params, mod_state, opt_state, x, y,
                              k).compile()
        if inner_steps == 1:  # multi-step: while-body cost attribution
            # is backend-dependent, so the HLO cross-check only runs plain
            cost = compiled.cost_analysis() or {}
            flops_hlo = float(cost.get("flops", 0.0) or 0.0)
            # under SPMD cost_analysis reports the per-device partitioned
            # module; scale to global so both numerators share a basis
            if strat_name is not None:
                flops_hlo *= n_dev
        step = compiled
    step_flops = flops_analytic or flops_hlo
    mfu_basis = ("analytic" if flops_analytic
                 else ("hlo" if flops_hlo else None))

    peak_per_chip, peak_label = _peak_flops(jax.devices()[0])
    peak = peak_per_chip * n_dev if peak_per_chip else None
    if obs_state is not None and obs_state.enabled:
        # HBM attribution context (ISSUE 12): the static per-category
        # plan of the exact compiled step + a live sampler, installed
        # BEFORE the first execution so an OOM autopsy carries the plan
        from bigdl_tpu.obs import memory as _mem
        try:
            mem_plan = _mem.build_plan(
                compiled, params=params, opt_state=opt_state,
                batch=(x, y),
                grad_comm=(strat.grad_comm_info() if strat is not None
                           else None),
                device=jax.devices()[0], batch_size=batch,
                model_name=model_name)
            mem_sampler = _mem.HbmSampler()
            obs_state.mem_plan = mem_plan
            obs_state.mem_sampler = mem_sampler
            _mem.install(plan=mem_plan, sampler=mem_sampler)
        except Exception:  # the plan must never break the run it plans
            pass

    try:
        params, mod_state, opt_state, loss = step(params, mod_state,
                                                  opt_state, x, y, k)
        float(loss)  # warmup; the scalar host fetch is the sync
    except Exception as e:
        # first execution is where a genuinely-too-big step dies —
        # autopsy RESOURCE_EXHAUSTED (plan + live stats + top buffers
        # to --traceDir) before re-raising, like any other crash
        from bigdl_tpu.obs import memory as _mem
        _mem.handle_oom(e, "perf_warmup")
        raise

    if elastic is not None:
        # topology is live (mesh formed, step compiled, bucket bound
        # re-resolved in the fresh trace): report it — the call after a
        # caught DeviceLossFault closes out the reshape event with the
        # from/to counts, restore_ms, and bucket bound before/after
        info = strat.grad_comm_info() if strat is not None else None
        elastic.observe_topology(
            n_dev, bucket_bytes=(info or {}).get("bucket_bytes"),
            restore_ms=(time.perf_counter() - t_attempt0) * 1000.0)

    feed = None
    pipeline_prov = None
    if data_source is not None:
        if not data_source.startswith("record:"):
            raise SystemExit(f"unknown --data source {data_source!r}")
        src_path = data_source[len("record:"):]
        # image models: crop records to the model's own spatial dims
        # (224 for the ImageNet family, 32 for resnet20_cifar, ...)
        crop = (tuple(in_shape[:2])
                if len(in_shape) == 3 and in_shape[2] == 3 else (224, 224))
        if data_workers > 0 or stage != "off":
            # ISSUE 13: the executor pipeline replaces the legacy
            # windowed thread-pool feed; --stage device commits the
            # batch to the strategy's sharded layout off-thread
            feed, sig = _executor_record_batches(
                src_path, batch, workers=max(1, data_workers),
                depth=prefetch_depth, stage=stage, strategy=strat,
                crop=crop)
            pipeline_prov = {"workers": max(1, data_workers),
                             "depth": prefetch_depth, "stage": stage,
                             "signature": sig}
        else:
            feed = _record_batches(src_path, batch, crop=crop)
        next(feed)  # warm the decode pool outside the timed region

    import contextlib
    trace_cm = contextlib.nullcontext()
    if profile_dir:
        # xplane trace of the timed region (feeds scripts/mfu_experiment
        # style analysis; view with tensorboard or xprof tooling)
        trace_cm = jax.profiler.trace(profile_dir)

    from bigdl_tpu.resilience.faults import hook as _fault_hook

    # --obs: per-step phase metering (ISSUE 7). The obs-on loop is a
    # separate branch so the obs-off loop stays UNTOUCHED — obs-off
    # output must be byte-identical to pre-obs output (modulo the null
    # phase columns), and the per-step block_until_ready that makes
    # device time exact costs dispatch pipelining (that delta IS the
    # obs overhead; not measured on the chip).
    obs_on = obs_state is not None and obs_state.enabled
    capture = obs_state.capture if obs_state is not None else None
    phase = None
    t0 = time.perf_counter()
    with trace_cm:
        if obs_on:
            from bigdl_tpu.obs import (get_registry, phase_histograms,
                                       span)
            phase = {p: 0.0 for p in ("data_wait", "h2d", "dispatch",
                                      "device", "ckpt")}
            hists = phase_histograms(get_registry(), "train")
            mem_sampler = getattr(obs_state, "mem_sampler", None)
            pc = time.perf_counter

            def _meter(name, t_start):
                d = pc() - t_start
                phase[name] += d
                hists[name].observe(d * 1000.0)

            for i in range(iterations):
                if capture is not None:
                    capture.on_step(i)
                if feed is not None:
                    t = pc()
                    with span("data_wait"):
                        mb = next(feed)
                    _meter("data_wait", t)
                    t = pc()
                    with span("h2d"):
                        # staged feeds already committed the batch to
                        # device (producer thread recorded the h2d span);
                        # the asarray here would be a no-op aliasing
                        x, y = mb.input, mb.target
                        if not isinstance(x, jax.Array):
                            x = jnp.asarray(x)
                            y = jnp.asarray(y)
                    _meter("h2d", t)
                _fault_hook("step")
                t = pc()
                with span("dispatch"):
                    params, mod_state, opt_state, loss = step(
                        params, mod_state, opt_state, x, y, k)
                _meter("dispatch", t)
                t = pc()
                with span("device"):
                    jax.block_until_ready(loss)
                _meter("device", t)
                if mem_sampler is not None:
                    # live HBM gauges + Chrome-trace counter series (a
                    # cheap None on backends without memory_stats)
                    mem_sampler.sample(step=i)
            float(loss)
            reg = get_registry()
            for p_name, secs in phase.items():
                if secs > 0.0:
                    reg.counter(
                        f"train_phase_{p_name}_seconds_total",
                        f"cumulative {p_name} phase seconds").inc(secs)
        else:
            for _ in range(iterations):
                if feed is not None:
                    mb = next(feed)
                    x, y = mb.input, mb.target
                    if not isinstance(x, jax.Array):
                        x = jnp.asarray(x)   # host->device each step,
                        y = jnp.asarray(y)   # as in a real epoch
                # fault site (one pointer check when no --faultPlan)
                _fault_hook("step")
                params, mod_state, opt_state, loss = step(
                    params, mod_state, opt_state, x, y, k)
            float(loss)  # scalar host read = true device sync (above)
    dt = time.perf_counter() - t0

    total_steps = iterations * inner_steps
    ips = batch * total_steps / dt
    mfu = ((step_flops * total_steps / dt) / peak
           if step_flops and peak else None)
    out = {
        "model": model_name,
        "batch": batch,
        "iterations": iterations,
        "inner_steps": inner_steps,
        # ISSUE 8: strategy + mesh topology in EVERY line — a multichip
        # row must say which axes its collectives rode (null/1/null on
        # a single-device run, schema stable)
        "strategy": strat_name,
        "n_devices": n_dev,
        "mesh": mesh_axes,
        # ISSUE 10: what the gradient wire carried — every line says so
        # ("off"/null single-device or uncompressed, so compressed-vs-
        # plain A/Bs join on schema-stable columns)
        "grad_compress": (grad_comm_cfg.compress
                          if (grad_comm_cfg is not None
                              and grad_comm_cfg.active
                              and strat is not None) else "off"),
        "grad_buckets": (strat.grad_comm_info()["n_buckets"]
                         if (strat is not None
                             and strat.grad_comm_info() is not None)
                         else None),
        "seconds": round(dt, 4),
        "records_per_second": round(ips, 2),
        "images_per_second_per_chip": round(ips / n_dev, 2),
        "dtype": str(dtype.__name__ if hasattr(dtype, "__name__")
                     else dtype),
        # MFU is a FRACTION in [0,1]; mfu_pct is the human-facing percent
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_pct": round(mfu * 100, 2) if mfu is not None else None,
        "mfu_basis": mfu_basis,
        "peak_flops_assumed": peak_per_chip,
        "peak_flops_device_match": peak_label,
        "step_gflops_analytic": round(flops_analytic / 1e9, 3),
        # the matmul/conv split of the analytic numerator
        "step_gflops_by_kind": {
            "matmul": round(flops_kinds["matmul"] / 1e9, 3),
            "conv": round(flops_kinds["conv"] / 1e9, 3)},
        "step_gflops_hlo": round(flops_hlo / 1e9, 3),
        # loss parity anchor: a --strategy run must land where the
        # single-device run lands (the DistriOptimizerSpec bar)
        "final_loss": round(float(loss), 6),
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        # which Pallas kernels the compiled step runs through Mosaic
        # ([] off-TPU without reading the HLO: interpreted kernels are
        # plain HLO there; null for pp, whose step is not AOT-compiled)
        "mosaic_kernels": ([] if not on_tpu else mosaic_kernels(compiled)
                           if compiled is not None else None),
        # ISSUE 13: which feed machinery produced the batches — null on
        # the legacy window feed / synthetic data, so executor-vs-legacy
        # A/Bs join on a schema-stable column next to stall_frac
        "pipeline": pipeline_prov,
    }
    if strat is not None and strat.grad_comm_info() is not None:
        # the full wire accounting (bucket bound + provenance, wire
        # bytes vs f32 bytes, plan signature) for PERF.md §17 tables
        out["grad_comm"] = dict(strat.grad_comm_info())
    if elastic is not None:
        # ISSUE 11: the elastic columns. `reshape` is the most recent
        # mesh re-formation (from/to device counts, restore_ms, bucket
        # bound before/after + total count) or null when the topology
        # never changed; effective_batch exposes hold-padding/scale-
        # trimming (== batch at full topology)
        out["elastic"] = {"policy": elastic.batch_policy,
                          "min_devices": elastic.min_devices,
                          "effective_batch": int(x.shape[0])}
        out["reshape"] = elastic.reshape_annotation()
    _annotate_obs_phases(out, obs_state, phase, dt)
    _annotate_conv_layouts(out)
    _annotate_autotune(out)
    if model is not None:
        _annotate_bn_fused(out, model)
    else:
        out["bn_fused"] = "off"  # pp/ep harnesses carry no BN
    if lint is not None:  # --lint pre-flight summary rides in the JSON
        out["lint"] = lint  # line like bn_fused/autotune decisions do
    _annotate_supervisor(out, supervisor)
    if flops_error is not None:
        out["flops_analytic_error"] = flops_error
    if flops_analytic and flops_hlo:
        ratio = flops_hlo / flops_analytic
        if ratio > 2.0 or ratio < 0.5:
            out["flops_disagreement"] = round(ratio, 3)
    if is_lm:
        out["tokens_per_second"] = round(ips * in_shape[0], 1)
    print(json.dumps(out))
    return out


# synthetic-grade defaults: (band/chroma contrast, pixel-noise sigma).
# hard was tuned so resnet20 at CIFAR scale crosses 0.91 over multiple
# epochs; easy saturates in under an epoch (color cue)
HARD_GRADE = (8.0, 35.0)
EASY_GRADE = (55.0, 30.0)


def resolve_grade(hard: bool, lift: float | None,
                  noise: float | None) -> tuple[float, float]:
    """Effective (lift, noise) after applying grade defaults — also used
    to RECORD the effective values in result JSON (a null there could
    not tell which defaults generated archived data)."""
    g_lift, g_noise = HARD_GRADE if hard else EASY_GRADE
    return (g_lift if lift is None else lift,
            g_noise if noise is None else noise)


def _make_class_image_tree(root: str, classes: int, per_class: int,
                           size: int, seed: int = 0,
                           hard: bool = False,
                           lift: float | None = None,
                           noise: float | None = None) -> None:
    """Synthetic LEARNABLE image tree (zero-egress stand-in for ImageNet):
    easy grade gives each class a distinct mean color + a bright band at
    a class-specific height under pixel noise — decodable by a conv net
    but not linearly trivial. JPEG-encoded so the full decode+augment
    path runs.

    ``hard=True`` encodes the class as a SUBTLE MEAN-CHROMA DIRECTION:
    every class shares the same gray luminance; class c tints the image
    toward hue angle 2*pi*c/classes with per-pixel amplitude ``lift``
    (default 8) under noise sigma ``noise`` (default 35) — per-pixel SNR
    ~0.2, so the net must learn to pool chroma over the whole image.

    Why mean chroma: it is the only signal family that survives the
    training pipeline's standard augmentation unchanged. Two earlier
    hard grades failed measurably at 50k scale: (1) band *position* —
    the 8/7-headroom random crop translates train images by up to ~5 px,
    more than the 3.2 px between band positions, so train labels become
    inconsistent while val center-crops stay clean (train loss ~0, val
    plateau 0.46); (2) stripe *period* — train's 8/7 resize rescales
    every period by 1.156x relative to val's scale-to-fill, so the
    train-learned frequency classes systematically miss the val
    frequencies (val collapses to chance). Mean chroma is invariant to
    resize, crop, hflip, and JPEG 4:2:0 chroma subsampling.
    ``lift``/``noise`` override the grade's contrast and noise sigma."""
    import numpy as np
    from PIL import Image

    lift, noise = resolve_grade(hard, lift, noise)
    # chroma basis exactly orthogonal to Rec.601 luma (0.299,0.587,0.114)
    # so the full-resolution JPEG Y channel carries ZERO class signal for
    # every angle — otherwise classes near ang=+-90 deg would be partly
    # readable from luminance and per-class difficulty would be skewed
    _luma = np.array([0.299, 0.587, 0.114], np.float32)
    _v1 = np.array([0.587, -0.299, 0.0], np.float32)
    _v1 /= np.linalg.norm(_v1)
    _v2 = np.cross(_luma, _v1)
    _v2 /= np.linalg.norm(_v2)

    rs = np.random.RandomState(seed)
    for c in range(classes):
        d = os.path.join(root, f"class{c:03d}")
        os.makedirs(d, exist_ok=True)
        if hard:
            ang = 2.0 * np.pi * c / classes
            # 1.22 ~= sqrt(1.5): keeps total chroma power at the level
            # the grade's lift default was tuned at
            chroma = 1.22 * (np.cos(ang) * _v1 + np.sin(ang) * _v2)
            hue = np.full(3, 110.0, np.float32)
        else:
            chroma = None
            hue = np.array([(40 + c * 53) % 200, (60 + c * 97) % 200,
                            (80 + c * 151) % 200], np.float32)
        band = (c * size) // classes
        bh = max(2, size // classes)
        for i in range(per_class):
            img = np.broadcast_to(hue, (size, size, 3)).copy()
            if hard:
                img += chroma * lift
            else:
                img[band:band + bh] += lift
            img += rs.randn(size, size, 3) * noise
            Image.fromarray(
                np.clip(img, 0, 255).astype(np.uint8)).save(
                os.path.join(d, f"{i:04d}.jpg"), quality=85)


def run_time_to_acc(model_name: str, batch: int, target: float,
                    max_epochs: int = 40, image_size: int = 64,
                    classes: int = 10, train_per_class: int = 200,
                    val_per_class: int = 40, learning_rate: float = 0.1,
                    use_bf16: bool = True, data_dir: str | None = None,
                    hard: bool = False, val_every_iters: int | None = None,
                    lift: float | None = None, noise: float | None = None,
                    weight_decay: float = 1e-4,
                    fused_bn: str | None = None,
                    lint: dict | None = None,
                    supervisor=None, obs_state=None,
                    grad_compress: str | None = None,
                    grad_buckets: str | None = None):
    """Time-to-accuracy harness (BASELINE.json metric: images/sec/chip
    **+ time-to-76%-top1**; reference recipe models/inception/Train.scala
    :77-83 + scripts/run.example.sh:54). Trains ``model_name`` from
    RECORD SHARDS (decode+augment in the timed path, like the reference's
    SequenceFile flow), validates top-1 each epoch against wall clock,
    stops at ``target`` via Trigger.max_score, and reports the first
    crossing time from the val curve. Zero-egress sandbox ⇒ the dataset
    is synthetic-but-learnable (_make_class_image_tree); on real ImageNet
    shards pass ``data_dir`` with train/ and val/ record subdirs plus
    ``classes=1000`` and target=0.76."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import tuning
    tuning.reset_decisions()  # annotate only THIS run's consulted keys

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import RecordImageDataSet, write_image_shards
    from bigdl_tpu.optim import (Optimizer, SGD, Top1Accuracy, Trigger)
    from bigdl_tpu.parallel import DataParallel, local_mesh

    t_setup = time.time()
    td = None
    summary_dir = tempfile.mkdtemp(prefix="tta_summary_")
    try:
        if data_dir is None:
            td = tempfile.mkdtemp(prefix="tta_")
            for split, per in (("train", train_per_class),
                               ("val", val_per_class)):
                tree = os.path.join(td, "imgs", split)
                _make_class_image_tree(tree, classes, per, image_size,
                                       seed=0 if split == "train" else 1,
                                       hard=hard, lift=lift, noise=noise)
                write_image_shards(tree, os.path.join(td, "shards", split),
                                   prefix=split, images_per_shard=256,
                                   workers=4)
            data_dir = os.path.join(td, "shards")

        mean, std = [127.0] * 3, [60.0] * 3
        crop = (image_size, image_size)
        train_ds = RecordImageDataSet(os.path.join(data_dir, "train"),
                                      batch, crop=crop, train=True,
                                      mean=mean, std=std)
        val_ds = RecordImageDataSet(os.path.join(data_dir, "val"), batch,
                                    crop=crop, train=False, mean=mean,
                                    std=std)

        model, _ = build_model(model_name, class_num=classes)
        from bigdl_tpu.cli.common import apply_fused_bn
        apply_fused_bn(model, fused_bn)
        from bigdl_tpu.parallel.grad_comm import make_config as _mk_gc
        try:
            gc_cfg = _mk_gc(grad_compress, grad_buckets)
        except ValueError as e:
            raise SystemExit(str(e))
        strat = DataParallel(local_mesh(), grad_comm=gc_cfg)
        opt = Optimizer(
            model, train_ds, nn.ClassNLLCriterion(),
            # wd matches the reference CIFAR recipe (models/resnet/README.md
            # Training: lr 0.1, momentum 0.9, weight decay 1e-4) — without
            # it the 50k-scale hard grade memorizes its pixel noise
            optim_method=SGD(learning_rate=learning_rate, momentum=0.9,
                             weight_decay=weight_decay),
            end_when=Trigger.or_(Trigger.max_epoch(max_epochs),
                                 Trigger.max_score(target)),
            strategy=strat,
            compute_dtype=(jnp.bfloat16 if use_bf16 else None))
        val_trig = (Trigger.several_iteration(val_every_iters)
                    if val_every_iters else Trigger.every_epoch())
        opt.set_validation(val_trig, val_ds, [Top1Accuracy()])
        opt.set_summary(summary_dir)
        if obs_state is not None and obs_state.capture is not None:
            opt.set_capture(obs_state.capture)

        t_train = time.time()
        opt.optimize()
        wall = time.time() - t_train

        curve = []
        with open(os.path.join(summary_dir, "val.jsonl")) as f:
            for line in f:
                curve.append(json.loads(line))
    finally:
        if td is not None:
            shutil.rmtree(td, ignore_errors=True)
        shutil.rmtree(summary_dir, ignore_errors=True)
    reached = [r for r in curve if r.get("top1_accuracy", 0.0) >= target]
    out = {
        "model": model_name,
        "metric": "time_to_acc",
        "target_top1": target,
        "reached": bool(reached),
        "time_to_acc_s": (round(reached[0]["wall_s"], 2) if reached
                          else None),
        "train_wall_s": round(wall, 2),
        "setup_s": round(t_train - t_setup, 2),
        "final_top1": curve[-1]["top1_accuracy"] if curve else None,
        # distinct epoch stamps across val points: equals the epoch count
        # under every-epoch validation, and "epochs touched" under
        # --valEvery (the val row's epoch field is post-rollover)
        "epochs_run": len({r.get("epoch") for r in curve}),
        "val_points": len(curve),
        # schema-stable grad-comm columns (ISSUE 10) — the tta line
        # carries them like every perf line does
        "grad_compress": (gc_cfg.compress
                          if (gc_cfg is not None and gc_cfg.active
                              and strat.grad_comm_info() is not None)
                          else "off"),
        "grad_buckets": (strat.grad_comm_info()["n_buckets"]
                         if strat.grad_comm_info() is not None else None),
        "hard_data": hard,
        "grade_lift": resolve_grade(hard, lift, noise)[0],
        "grade_noise": resolve_grade(hard, lift, noise)[1],
        "weight_decay": weight_decay,
        "batch": batch,
        "image_size": image_size,
        "classes": classes,
        "device": jax.devices()[0].device_kind,
        "curve": [{"wall_s": r.get("wall_s"),
                   "top1": r.get("top1_accuracy")} for r in curve],
    }
    _annotate_obs_phases(out, obs_state, opt.phase_totals(), wall)
    _annotate_conv_layouts(out)
    _annotate_autotune(out)
    _annotate_bn_fused(out, model)
    if lint is not None:
        out["lint"] = lint
    _annotate_supervisor(out, supervisor)
    print(json.dumps(out))
    return out


def main(argv=None):
    p = argparse.ArgumentParser("bigdl-tpu perf")
    p.add_argument("-m", "--model", default="inception_v1")
    p.add_argument("-b", "--batchSize", type=int, default=32)
    p.add_argument("-i", "--iteration", type=int, default=10)
    p.add_argument("--dataType", choices=["constant", "random"],
                   default="constant")
    p.add_argument("--f32", action="store_true",
                   help="disable bf16 compute")
    p.add_argument("--dataParallel", action="store_true",
                   help="deprecated alias for --strategy dp")
    p.add_argument("--seq", type=int, default=None,
                   help="override the transformer_lm* sequence length "
                        "(mirrors lint's --seq; shrinks CPU --strategy "
                        "smokes to seconds)")
    p.add_argument("--data", default=None,
                   help="feed from storage instead of a resident batch, "
                        "e.g. record:/path/to/shards (timed loop then "
                        "includes decode+augment+host->device)")
    p.add_argument("--innerSteps", type=int, default=1,
                   help="steps chained inside one compiled program "
                        "(amortizes dispatch overhead)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler xplane trace of the timed "
                        "loop to DIR")
    p.add_argument("--timeToAcc", type=float, default=None, metavar="T",
                   help="run the time-to-accuracy harness instead of the "
                        "throughput loop: train from record shards to "
                        "val top1 >= T (BASELINE metric "
                        "'time-to-76%%-top1'; synthetic learnable data "
                        "unless --data record:DIR points at real shards)")
    p.add_argument("--maxEpoch", type=int, default=40,
                   help="epoch cap for --timeToAcc")
    p.add_argument("--imageSize", type=int, default=64,
                   help="image side for --timeToAcc synthetic data")
    p.add_argument("--classes", type=int, default=10,
                   help="class count for --timeToAcc (pass 1000 with real "
                        "ImageNet shards via --data record:DIR)")
    p.add_argument("--trainPerClass", type=int, default=200,
                   help="synthetic train images per class for --timeToAcc "
                        "(5000 = CIFAR-10 scale, the reference recipe "
                        "models/resnet/README.md Training section)")
    p.add_argument("--valPerClass", type=int, default=40,
                   help="synthetic val images per class for --timeToAcc "
                        "(1000 = CIFAR-10 scale)")
    p.add_argument("--ttaHard", action="store_true",
                   help="harder synthetic classes (band position only, "
                        "no color cue) so the accuracy curve spans "
                        "multiple epochs")
    p.add_argument("--valEvery", type=int, default=None, metavar="ITERS",
                   help="validate every N iterations instead of every "
                        "epoch (denser accuracy-vs-wall-clock curve)")
    p.add_argument("--ttaLift", type=float, default=None,
                   help="override the synthetic grade's contrast: chroma "
                        "amplitude for --ttaHard (default 8), band "
                        "contrast for easy (default 55)")
    p.add_argument("--ttaNoise", type=float, default=None,
                   help="override the synthetic grade's pixel-noise sigma "
                        "(hard default 35, easy 30)")
    p.add_argument("--ttaWd", type=float, default=1e-4,
                   help="weight decay for --timeToAcc (reference CIFAR "
                        "recipe value 1e-4)")
    p.add_argument("--convLayout", default=None, metavar="FWD,DGRAD,WGRAD",
                   help="per-pass conv activation layouts (NHWC|NCHW|GEMM "
                        "each, or 'auto'/'default') — e.g. a "
                        "scripts/conv_bwd_probe.py decision via "
                        "scripts/apply_conv_probe.py. GEMM runs eligible "
                        "1x1/stride-1 convs as dot_general (exact-parity "
                        "NHWC fallback elsewhere). Unset = 'auto': the "
                        "measured decision shipped for this device kind "
                        "(ops/conv2d.MEASURED_DECISIONS), no-op on "
                        "unmeasured devices; 'default' forces all-NHWC. "
                        "An explicit spec wins over --convGeom and the "
                        "autotuner")
    p.add_argument("--convGeom", default=None, metavar="FILE",
                   help="per-conv-geometry layout decision JSON "
                        "(scripts/apply_conv_probe.py --geom output): "
                        "keys decisions by (kh, kw, stride, cin, cout, "
                        "groups, dilation, dtype) so e.g. the stem's "
                        "wgrad runs NCHW while 3x3 stages stay NHWC and "
                        "1x1/s1 convs may run as GEMM; stamped as "
                        "conv_geom in the result JSON")
    from bigdl_tpu.cli.common import (_add_platform_arg, add_autotune_arg,
                                      add_fused_bn_arg, add_grad_comm_args,
                                      add_lint_arg, add_obs_args,
                                      add_pipeline_args,
                                      add_resilience_args,
                                      add_strategy_arg, apply_platform,
                                      run_preflight_lint)
    _add_platform_arg(p)
    add_strategy_arg(p)
    add_grad_comm_args(p)
    add_autotune_arg(p)
    add_fused_bn_arg(p)
    add_lint_arg(p)
    add_resilience_args(p)
    add_obs_args(p)
    add_pipeline_args(p)
    args = p.parse_args(argv)
    apply_platform(args)  # also installs --faultPlan and --obs
    if args.convLayout:
        # apply_platform already installed the spec (SystemExit on a bad
        # one); just surface what's active for the capture logs
        from bigdl_tpu.ops.conv2d import get_conv_pass_layouts
        print("conv pass layouts:", get_conv_pass_layouts(), flush=True)
    lint_ann = None
    if args.lint:
        # pre-flight static analysis of THIS run's model/config
        # (bigdl_tpu.analysis; PERF.md §12 + §26) — the ResolvedConfig
        # spine resolves the mirrored flag families once, shardlint
        # traces the sharded step over this run's REAL device count,
        # strict refuses to launch on error-severity findings, and the
        # summary is stamped into the result JSON either way
        import jax

        from bigdl_tpu.analysis import lint_config
        from bigdl_tpu.cli.common import resolve_lint_config
        cfg = resolve_lint_config(args, n_devices=len(jax.devices()))
        report = lint_config(cfg)
        rc, lint_ann = run_preflight_lint(
            report, strict=(args.lint == "strict"))
        if lint_ann is not None and cfg.mesh:
            lint_ann["mesh"] = ",".join(
                f"{a}:{s}" for a, s in cfg.mesh_axes)
        if rc:
            return rc
    obs_state = getattr(args, "_obs", None)

    def _go(supervisor=None, elastic=None):
        if args.timeToAcc is not None:
            if args.strategy and args.strategy != "dp":
                raise SystemExit(
                    "--timeToAcc trains through the Optimizer, which is "
                    "data-parallel by construction — --strategy only "
                    "composes with the throughput loop (dp is implied "
                    "here)")
            data_dir = None
            if args.data and args.data.startswith("record:"):
                data_dir = args.data[len("record:"):]
            run_time_to_acc(args.model, args.batchSize, args.timeToAcc,
                            max_epochs=args.maxEpoch,
                            image_size=args.imageSize,
                            classes=args.classes,
                            train_per_class=args.trainPerClass,
                            val_per_class=args.valPerClass,
                            use_bf16=not args.f32, data_dir=data_dir,
                            hard=args.ttaHard,
                            val_every_iters=args.valEvery,
                            lift=args.ttaLift, noise=args.ttaNoise,
                            weight_decay=args.ttaWd, fused_bn=args.fusedBN,
                            lint=lint_ann, supervisor=supervisor,
                            obs_state=obs_state,
                            grad_compress=args.gradCompress,
                            grad_buckets=args.gradBuckets)
            return
        run(args.model, args.batchSize, args.iteration, args.dataType,
            use_bf16=not args.f32, data_parallel=args.dataParallel,
            data_source=args.data, inner_steps=args.innerSteps,
            profile_dir=args.profile, fused_bn=args.fusedBN,
            lint=lint_ann, supervisor=supervisor, obs_state=obs_state,
            strategy=args.strategy, seq_len=args.seq,
            grad_compress=args.gradCompress, grad_buckets=args.gradBuckets,
            elastic=elastic, data_workers=args.dataWorkers,
            prefetch_depth=args.prefetchDepth, stage=args.stage)

    if args.elastic is not None:
        # elastic perf (ISSUE 11): a kill_device fault mid-loop marks
        # the victims lost and raises DeviceLossFault; the retry probes
        # the survivors, re-forms the mesh at the smaller count, pads or
        # trims the batch per --elastic, and the JSON line carries the
        # reshape dict. Below --minDevices the run gives up cleanly.
        if args.timeToAcc is not None:
            raise SystemExit(
                "--elastic + --timeToAcc: use the training CLIs (their "
                "run_optimize path reshapes through checkpoint resume); "
                "the perf throughput loop is the elastic harness here")
        from bigdl_tpu.resilience.elastic import ElasticSupervisor
        from bigdl_tpu.resilience.supervisor import (RetryPolicy,
                                                     SupervisorGaveUp)
        sup = ElasticSupervisor(
            RetryPolicy(budget=(args.supervise if args.supervise is not None
                                else 5)),
            min_devices=args.minDevices, batch_policy=args.elastic,
            name="perf")
        try:
            sup.run(lambda _n: _go(sup, elastic=sup))
        except SupervisorGaveUp as e:
            raise SystemExit(f"elastic: {e}")
        return

    if args.supervise is not None:
        # supervised perf: transient injected faults retry with backoff
        # and the fault/recovery log rides in the JSON line; fault-free,
        # the timed loop is unchanged (one pointer check per step)
        from bigdl_tpu.resilience.supervisor import RetryPolicy, Supervisor
        sup = Supervisor(RetryPolicy(budget=args.supervise), name="perf")
        sup.run(lambda _n: _go(sup))
        return
    _go()


if __name__ == "__main__":
    main()
