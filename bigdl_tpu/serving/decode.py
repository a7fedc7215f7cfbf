"""Incremental generation for the LMs (``transformer_lm``, ``sambay_lm``,
``hybrid_moe_lm``) with a preallocated cache and continuous-batching slots.

What a slot may hold is the model's to say: the engine asks it for
``init_cache(batch, max_len, dtype)`` and ``prompt_buckets(max_len,
dtype)`` and treats the cache as a pytree whose leaves all have the slot
as their first axis. ``TransformerLM`` answers with K and V of ``max_len``
rows a layer. ``SambaYLM`` answers with four kinds of leaf side by side: a
ring of ``window`` K/V rows for each window layer, ``max_len`` K/V rows
for its one shared layer, and for each state-space layer a float32 scan
state and the convolution's last rows. ``HybridMoELM`` answers with
``max_len`` K/V rows for each softmax layer and, for each linear-attention
layer, a float32 matrix state a head and the convolution's last rows; its
step also counts on the device (which held experts each slot's token
chose), and a model that offers ``decode_logits_stats`` /
``step_counters`` / ``count_step`` has those counts added to the engine's
counters when the step retires. A prefill hands over a whole slot
(``_write_slot``), so a reused slot keeps nothing of the last request, and
the model's ``prefill_logits`` stops every leaf at the prompt's last real
token whatever the bucket's padding. Page pools, the prefix cache,
speculation, kv8 and tp placement below assume per-layer K/V rows and
dense MLP weights: for a model that declares ``recurrent_state`` the
engine refuses them, and names the routed expert stack beside the state
where a model declares ``routed_experts``.

``TransformerLM.generate`` is the offline shape of decoding: one request,
one fori_loop, prompt and token budget baked into the compile. An online
server cannot afford that — every (prompt_len, max_new) pair would be a
fresh XLA program, and concurrent requests would each run their own
batch-1 decode at ~1/slots of the achievable throughput. This module
splits decoding the way serving systems do (Orca-style continuous
batching):

* **prefill** — one compiled program per PROMPT-LENGTH BUCKET
  (``ops.attention_kernel.serving_prefill_buckets`` keeps the ladder on
  the flash kernel's zero-padding block plans): the prompt, right-padded
  to its bucket, runs once through ``model.prefill_logits`` building a
  batch-1 K/V cache, exact because causal attention never reads past the
  true last position and decode overwrites pad K/V before attending it;

* **decode** — ONE compiled per-token step over all ``slots``
  (``jax.vmap`` of ``model.decode_logits`` with per-slot positions), so
  requests of different lengths and arrival times share the batch. A
  finishing request frees its slot; the next waiting request prefills
  into it while the others keep decoding. A step reads every weight and,
  in attention, each slot's LIVE cache rows once at their stored
  ``num_kv_heads`` (``MultiHeadAttention.decode_chunk``): one kernel call
  a reading layer, ``ceil(position / 512)`` row blocks of a slot and not
  its ``max_len`` rows (``ops.cache_attention.attend_rows``, whose
  batching rule replaces the whole-cache read ``vmap`` would make).
  ``decode_live_positions_total`` over ``decode_steps_total`` says how
  many rows a step needed. The step writes each slot's new K and V rows
  at that slot's own position with one in-place kernel call a cache leaf
  (``ops.cache_write.write_rows``, likewise). An engine with a mesh
  keeps the scatter and the whole read, a paged engine the whole read:
  ``/debug/slots`` says which (``kv.row_write``, ``kv.cache_read``).

ISSUE 14 rebuilt the hot path around three composable optimisations:

* **sampling modes** — temperature / top-k / top-p with PER-REQUEST
  seeds (``spec_decode.warp_logits``; randomness is counter-based off
  the seed, so outputs are deterministic and replayable). The sort-free
  program still serves requests that only use temperature.
* **speculative decoding** (``speculate=K``) — a draft LM proposes K
  tokens per round, the target scores all K+1 positions in ONE chunked
  ``verify_logits`` dispatch, and exact acceptance keeps greedy output
  bit-identical / sampled output distribution-correct
  (:mod:`bigdl_tpu.serving.spec_decode`). Target dispatches per emitted
  token drop from 1 to 1/(accepted+1).
* **paged KV** (``kv_page_tokens=N``) — the dense ``slots x max_len``
  cache becomes pools of N-token pages with per-slot page tables
  (:mod:`bigdl_tpu.serving.kv_pages`); short requests stop paying
  max-length HBM (``kv_cache_bytes`` now reports ALLOCATED pages) and
  admission reserves a request's full page budget up front so decode
  never deadlocks mid-flight.
* **shared-prefix cache** (``prefix_cache=True``, needs paging) —
  prefills whose page-aligned token prefix hashes to a cached entry
  copy resident pages and chunk-prefill only the suffix
  (:mod:`bigdl_tpu.serving.prefix_cache`).

Greedy decoding (temperature 0) is bit-exact with the offline
full-sequence argmax decode (the acceptance contract; see
tests/test_serving.py) because both run the same ``prefill_logits`` /
``decode_logits`` graph per token — and speculative greedy is pinned
bit-identical to that in tests/test_spec_decode.py.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import threading
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.obs.spans import span as _obs_span
from bigdl_tpu.serving import kv_pages as _kvp
from bigdl_tpu.serving import spec_decode as _spec
from bigdl_tpu.serving.batcher import (AdmissionError, DeadlineExceeded,
                                       WorkerDied, _Future)
from bigdl_tpu.serving.prefix_cache import PrefixCache
from bigdl_tpu.serving.reqtrace import get as _get_reqtracer

logger = logging.getLogger(__name__)

__all__ = ["DecodeEngine", "DecodeRequest"]


class DecodeRequest:
    __slots__ = ("tokens", "max_new_tokens", "temperature", "stop_token",
                 "top_k", "top_p", "seed", "future", "out", "deadline",
                 "rid", "emit", "t_queued")

    def __init__(self, tokens, max_new_tokens, temperature=0.0,
                 stop_token=None, deadline=None, top_k=0, top_p=1.0,
                 seed=0, rid=None, emit=None):
        self.tokens = [int(t) for t in tokens]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.stop_token = stop_token
        self.deadline = deadline
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF
        self.future = _Future()
        self.out: list = []
        self.rid = rid  # lifecycle-trace request id (ISSUE 15)
        # streaming sink (ISSUE 18): called as emit(new_tokens, done)
        # after every round that appended tokens — only ACCEPTED tokens
        # reach it on the speculative path, so streamed output is
        # structurally identical to the buffered future result
        self.emit = emit
        self.t_queued = None  # engine clock when it joined the queue


# A plain step the device has been handed and the host has not read:
# the device array of its tokens, the (slot, request, position) of every
# slot it advanced, and what the model's step counted on the device (None
# for a model that counts nothing).
_Flight = collections.namedtuple("_Flight", "toks advanced stats",
                                 defaults=(None,))


def _dense_path_only(model, **asked) -> Optional[str]:
    """What ``DecodeEngine`` refuses ``model``, from what the model
    declares: ``recurrent_state`` (a slot holds a scan state, and a ring
    beside it: only the dense slab carries those), ``window`` without it
    (a slot holds rings of ``window`` K/V rows), ``routed_experts`` (a
    stack no 8-bit form or tp layout knows). ``asked``: the engine's
    options that are on. The message, or None where nothing asked for is
    refused. A model that declares ``latent_cache`` (attention through a
    low-rank latent) is refused whatever is asked: its slots would hold
    the latent cache, which is not built."""
    if getattr(model, "latent_cache", False):
        return (f"{type(model).__name__} attends through a low-rank latent "
                "(MLA) and trains only: a slot would hold the latent cache "
                "(c_kv and the shared rotated key a layer), which is not "
                "built; not supported yet: serving")
    state = getattr(model, "recurrent_state", False)
    ring = bool(getattr(model, "window", None)) and not state
    experts = getattr(model, "routed_experts", False)
    quant_stack = "the routed expert stack"
    mesh_stack = quant_stack + " and its exchange"
    if state:
        reasons = {
            "kv_page_tokens": "page pools hold per-layer K/V rows only "
                              "(serving/kv_pages.py)",
            "prefix_cache": "a shared prefix is a page copy, and the state "
                            "after the prefix is in no page "
                            "(serving/prefix_cache.py)",
            "speculate": "a rejected draft token cannot be taken out of a "
                         "scan state (serving/spec_decode.py)",
            "quantize": "no 8-bit form of the state-space weights or of "
                        "the state" + (f", nor of {quant_stack}"
                                       if experts else "")
                        + " (serving/quant.py)",
            "mesh": "no tp layout for the scan"
                    + (f", nor for {mesh_stack}" if experts else "")
                    + " (serving/sharding.py)"}
        keeps = "recurrent state in its slots"
    elif ring:
        reasons = {
            "kv_page_tokens": "a ring's rows are in no page: page pools "
                              "hold max_len K/V rows a layer "
                              "(serving/kv_pages.py)",
            "prefix_cache": "a shared prefix is a page copy, and a ring's "
                            "rows are in no page (serving/prefix_cache.py)",
            "speculate": "a rejected draft token has overwritten the row "
                         "of position pos - window in every ring "
                         "(serving/spec_decode.py)"}
        if experts:
            reasons["quantize"] = (f"no 8-bit form of {quant_stack} "
                                   "(serving/quant.py)")
            reasons["mesh"] = (f"no tp layout for {mesh_stack} "
                               "(serving/sharding.py)")
        keeps = "window rings in its slots"
    else:
        return None
    missing = [f"{name}: {reasons[name]}" for name, on in asked.items()
               if on and name in reasons]
    if not missing:
        return None
    return (f"{type(model).__name__} keeps {keeps}"
            + (" and routed expert stacks in its layers" if experts else "")
            + " and serves on the dense path only; not supported yet: "
            + "; ".join(missing))


class DecodeEngine:
    """Continuous-batching KV-cache decoder over a fixed slot count.

    ``slots`` bounds the decode batch (and, dense, the cache HBM
    footprint: slots x layers x kv_heads x max_len x head_dim x 2;
    paged, the page-table width — HBM then follows ALLOCATED pages).
    ``submit`` assigns a free slot (prefill) or queues up to
    ``max_waiting`` requests, rejecting beyond that
    (:class:`AdmissionError` -> 429). ``step`` advances every active
    slot — one token each plain, up to ``speculate+1`` each
    speculative. Without a worker thread the caller drives ``step``
    (tests, ``generate``); ``start()`` launches the decode loop for the
    HTTP server.

    A plain step is two halves: a **dispatch** (``_dispatch``: hand the
    device the step, advance the host's positions, count it) and a
    **retire** (``_retire``: read its tokens, emit, finish, hand off).
    The step program samples on the device from the logits it carries,
    runs every slot whether live or not, and keys its randomness by
    ``(seed, position)``, so nothing on the device waits for the host's
    read. ``step()`` dispatches and retires the same step and leaves
    nothing in flight. The decode loop keeps one step in flight
    (``_flight``): under the lock it dispatches step n+1, then retires
    step n, so the device runs while the host emits. A dispatch advances
    a slot only while its request's budget is not covered by the tokens
    emitted plus the one in flight; any other slot runs, as a free slot
    does, at a position it may write junk to (its last one, or 0) with
    its output dropped. A retire emits a token only if the slot still
    holds the request the step advanced: one that ended meanwhile (stop
    token, ``cancel``, deadline) has its run-ahead token dropped
    (``decode_dropped_tokens_total``), and what that step wrote lands in
    rows the slot's next owner overwrites, since every later install is
    behind it in the device's queue. The tokens are those of the
    one-step-deep engine, bit for bit. The speculative round needs its
    accepted counts on the host before it can draft again and stays
    synchronous.

    * ``kv_page_tokens`` — page size in tokens; None keeps the dense
      layout. Must divide ``max_len``. ``pool_pages`` overrides the
      pool size (default = the dense footprint + ``prefix_cache``
      headroom).
    * ``speculate`` — draft chunk length K; 0 disables. ``draft_model``
      / ``draft_params`` supply the proposer (default: the target
      itself — "self-draft", 100% greedy acceptance, useful for
      dispatch-count wins and CI determinism).
    * ``prefix_cache`` — share page-aligned prompt-prefix K/V across
      requests (requires paging).
    * ``quantize`` — ``--quantize`` mode (ISSUE 17): int8/fp8 weights
      via ``serving.quant``, ``kv8`` stores the page pools 8-bit
      (requires paging). ``off``/None is byte-identical to the
      unquantized path — no quant code runs.
    """

    def __init__(self, model, params, *, slots: int = 4,
                 max_len: Optional[int] = None, cache_dtype=None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 max_waiting: int = 64, metrics=None,
                 clock=None, kv_page_tokens: Optional[int] = None,
                 pool_pages: Optional[int] = None, speculate: int = 0,
                 draft_model=None, draft_params=None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 mesh=None, model_axis: str = "model",
                 quantize: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        import time as _time

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        self.clock = clock or _time.monotonic
        self._worker_error: Optional[BaseException] = None
        self._last_beat = self.clock()
        self.model = model
        refused = _dense_path_only(
            model, kv_page_tokens=kv_page_tokens, prefix_cache=prefix_cache,
            speculate=speculate, quantize=quantize not in (None, "off"),
            mesh=mesh is not None)
        if refused:
            raise ValueError(refused)
        # ---- quantized serving (ISSUE 17): weights go 8-bit BEFORE tp
        # placement so each scale vector ships to the mesh alongside its
        # weight (column-split weight -> split scale). Idempotent: trees
        # cli/serve already quantized pass through untouched.
        from bigdl_tpu.serving import quant as _q
        self.quantize = quantize if quantize else "off"
        self._wfmt, self._kv8 = _q.parse_quantize(quantize)
        if self._wfmt is not None:
            params = _q.quantize_params(params, self._wfmt)
            if draft_model is not None and draft_params is not None:
                draft_params = _q.quantize_params(draft_params, self._wfmt)
        # ---- tp placement (ISSUE 16): params go to the mesh under the
        # Megatron layout, KV leaves split on the kv_heads dim, logits /
        # host scalars stay replicated. mesh=None keeps the single-chip
        # path byte-for-byte (a 1-device mesh = a pinned dp replica).
        self.mesh = mesh
        if mesh is not None:
            from bigdl_tpu.serving.sharding import ServingSharding
            self._shard = ServingSharding(mesh, axis=model_axis)
            params = self._shard.place_params(model, params)
        else:
            self._shard = None
        self.params = params
        self.slots = int(slots)
        self.max_len = int(max_len or model.max_len)
        self.cache_dtype = cache_dtype or model.compute_dtype or jnp.float32
        self.max_waiting = int(max_waiting)
        self.speculate = int(speculate)
        self._jax, self._jnp = jax, jnp

        if prompt_buckets is None:
            prompt_buckets = model.prompt_buckets(self.max_len,
                                                  self.cache_dtype)
        self.prompt_buckets = tuple(sorted(set(int(b)
                                               for b in prompt_buckets)))

        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._reqs: list = [None] * self.slots
        self._waiting: collections.deque = collections.deque()
        # submits that have entered but hold neither a slot nor a queue
        # place yet (blocked on the engine lock, or prefilling under it)
        self._admitting = 0
        self._admit_lock = threading.Lock()

        # ---- KV backend: dense slab or page pools (ISSUE 14) -------------
        self.page_tokens = int(kv_page_tokens) if kv_page_tokens else None
        self.paged = self.page_tokens is not None
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires kv_page_tokens "
                             "(prefix sharing is a page copy)")
        if self._kv8 and not self.paged:
            raise ValueError("--quantize kv8 requires kv_page_tokens "
                             "(8-bit KV is a page-pool layout)")
        if self.paged:
            extra = 0
            if prefix_cache and pool_pages is None:
                # headroom so a warm prefix cache never starves decode
                extra = prefix_cache_pages or (
                    self.max_len // self.page_tokens)
            self._kv = _kvp.PagedKvCache(
                model.encoder, slots=self.slots, max_len=self.max_len,
                page_tokens=self.page_tokens, dtype=self.cache_dtype,
                pool_pages=pool_pages, extra_pages=extra,
                sharding=(self._shard.kv_sharding
                          if self._shard is not None else None),
                quantized=self._kv8)
            self._cache = None
        else:
            self._kv = None
            self._cache = model.init_cache(
                self.slots, self.max_len, self.cache_dtype)
            if self._shard is not None:
                self._cache = self._shard.place_kv(self._cache)
        self._pfx = (PrefixCache(self._kv, max_pages=prefix_cache_pages,
                                 metrics=metrics)
                     if prefix_cache else None)

        self._logits = jnp.zeros((self.slots, model.vocab), jnp.float32)
        if self._shard is not None:
            self._logits = jax.device_put(self._logits,
                                          self._shard.replicated)
        self._pos = np.zeros(self.slots, np.int32)
        self._temp = np.zeros(self.slots, np.float32)
        self._topk = np.zeros(self.slots, np.int32)
        self._topp = np.ones(self.slots, np.float32)
        self._seed = np.zeros(self.slots, np.uint32)
        self._pending = np.zeros(self.slots, np.int32)  # speculative only
        self._flight: Optional[_Flight] = None  # the decode loop's alone
        self._round_calls = None  # a round's call counters, while it runs
        self._thread = None
        self._closed = False

        # ---- draft model (speculative) -----------------------------------
        if self.speculate > 0:
            self.draft_model = draft_model or model
            self.draft_params = (draft_params if draft_model is not None
                                 else params)
            if draft_model is not None and draft_params is None:
                raise ValueError("draft_model without draft_params")
            self._draft_dtype = (self.draft_model.compute_dtype
                                 or jnp.float32)
            self._draft_cache = self.draft_model.init_cache(
                self.slots, self.max_len, self._draft_dtype)
            if self._shard is not None:
                # a distinct draft model gets its own Megatron layout
                # (the self-draft default already shares the placed
                # target params)
                if draft_model is not None:
                    self.draft_params = self._shard.place_params(
                        self.draft_model, self.draft_params)
                self._draft_cache = self._shard.place_kv(self._draft_cache)
        else:
            self.draft_model = self.draft_params = None
            self._draft_cache = None

        self._init_metrics(metrics)
        self._build_programs()

    # -------------------------------------------------------------- metrics
    def _init_metrics(self, metrics) -> None:
        self.metrics = metrics
        if metrics is None:
            self._m_tokens = self._m_steps = self._m_prefills = None
            self._m_live_pos = self._m_window_pos = None
            self._m_runahead = self._m_dropped = None
            self._m_prompt_tokens = self._m_rejected = None
            self._m_bucket_tokens = self._m_window_tokens = None
            self._m_queued = self._m_queue_wait = None
            self._m_expired = self._m_dead = self._m_cancelled = None
            self._m_spec_prop = self._m_spec_acc = None
            self._m_draft_steps = None
            self._m_model = {}
            self._m_rounds = self._c_round = None
            self._c_call = self._c_read = None
            return
        self._m_tokens = metrics.counter(
            "generated_tokens_total", "decode tokens emitted")
        self._m_steps = metrics.counter(
            "decode_steps_total",
            "batched TARGET-model decode/verify steps executed")
        self._m_live_pos = metrics.counter(
            "decode_live_positions_total",
            "cache positions those steps had to read: the live slots' "
            "positions before each step (over decode_steps_total, "
            "against slots x max_len: the share of the cache in use)")
        self._m_window_pos = metrics.counter(
            "decode_window_positions_total",
            "ring rows those steps had to read in each window layer: the "
            "live slots' min(position, window) before each step (0 for a "
            "model without window layers)")
        # the round by thread CPU (ISSUE 39): each pair is a span's wall
        # and decode-thread CPU seconds; wall less CPU is time the thread
        # was not running (the interpreter lock, a lock, the runtime)
        self._m_rounds = metrics.counter(
            "decode_rounds_total",
            "decode rounds that had live slots (plain or speculative)")
        self._c_round = (
            metrics.counter("decode_round_seconds_total",
                            "seconds of those rounds, under the lock"),
            metrics.counter("decode_round_cpu_seconds_total",
                            "decode-thread CPU seconds of those rounds"))
        self._c_call = (
            metrics.counter("decode_dispatch_seconds_total",
                            "seconds of the rounds' program calls (the "
                            "step's, the speculative draft and verify, a "
                            "hand-off's prefill): wall over CPU is the "
                            "runtime pushing back on a full device queue"),
            metrics.counter("decode_dispatch_cpu_seconds_total",
                            "decode-thread CPU seconds of those calls"))
        self._c_read = (
            metrics.counter("decode_read_seconds_total",
                            "seconds the rounds waited for the device to "
                            "hand back a step's tokens"),
            metrics.counter("decode_read_cpu_seconds_total",
                            "decode-thread CPU seconds of those waits"))
        self._m_runahead = metrics.counter(
            "decode_runahead_steps_total",
            "of those steps, the ones dispatched while an earlier step's "
            "tokens were still unread (over decode_steps_total: how often "
            "the decode loop runs ahead of its own emit)")
        # what the model's own step counts (``step_counters``: name -> help)
        self._m_model = {
            name: metrics.counter(name, text) for name, text in
            getattr(self.model, "step_counters", {}).items()}
        self._m_dropped = metrics.counter(
            "decode_dropped_tokens_total",
            "tokens a run-ahead step computed for a slot whose request "
            "had ended by the time the step was retired, and dropped")
        self._m_prefills = metrics.counter(
            "prefills_total", "prompt prefills executed")
        self._m_prompt_tokens = metrics.counter(
            "prompt_tokens_total", "prompt tokens prefilled")
        self._m_bucket_tokens = metrics.counter(
            "prefill_bucket_tokens_total",
            "positions prefilled, padding included (the bucket length "
            "of every prefill)")
        self._m_window_tokens = metrics.counter(
            "prefill_window_tokens_total",
            "prompt tokens prefilled through the window kernel: tokens of "
            "prompts whose bucket is longer than the model's window, so "
            "that its window layers attend a band (0 for a model without "
            "window layers)")
        self._m_queued = metrics.counter(
            "decode_queued_total",
            "generate requests installed after waiting for a slot")
        self._m_queue_wait = metrics.counter(
            "decode_queue_wait_seconds_total",
            "seconds those requests waited, enqueue to install")
        self._m_rejected = metrics.counter(
            "decode_rejected_total",
            "generate requests fast-rejected (waiting queue full)")
        self._m_expired = metrics.counter(
            "decode_expired_total",
            "generate requests dropped on deadline expiry")
        self._m_dead = metrics.counter(
            "decode_dead_submit_total",
            "generate submits fast-failed (decode worker dead)")
        self._m_cancelled = metrics.counter(
            "decode_cancelled_total",
            "generate requests cancelled mid-flight (client disconnect)")
        metrics.gauge("decode_worker_up",
                      "1 while the decode loop is healthy",
                      fn=lambda: 0.0 if self._worker_error else 1.0)
        metrics.gauge("decode_slots_active", "occupied decode slots",
                      fn=lambda: sum(r is not None for r in self._reqs))
        metrics.gauge(
            "decode_tokens_per_second",
            "lifetime generated_tokens_total / uptime",
            fn=lambda: (self._m_tokens.value
                        / max(metrics.uptime_s(), 1e-9)))
        # KV-cache byte accounting (ISSUE 12, corrected by ISSUE 14):
        # paged mode reports ALLOCATED pages — the real resident cost —
        # not the dense max-len bound the gauges used to assume
        from bigdl_tpu.obs.memory import tree_bytes as _kv_bytes
        if self.paged:
            metrics.gauge("kv_cache_bytes",
                          "allocated KV page bytes (all slots + prefix "
                          "cache)",
                          fn=lambda: self._kv.allocated_bytes())
            metrics.gauge("kv_cache_bytes_per_slot",
                          "allocated KV page bytes / slots",
                          fn=lambda: (self._kv.allocated_bytes()
                                      / max(1, self.slots)))
            metrics.gauge("kv_pages_in_use", "KV pool pages handed out",
                          fn=lambda: self._kv.alloc.pages_in_use)
            metrics.gauge("kv_page_occupancy_frac",
                          "live tokens / (pages_in_use x page_tokens)",
                          fn=self._page_occupancy)
            logger.info(
                "decode KV pages: %d-token pages, pool %d pages "
                "(%d bytes; dense bound was %d bytes)",
                self.page_tokens, self._kv.pool_pages,
                self._kv.pool_bytes(),
                self.slots * self._kv.max_pages * self._kv.bytes_per_page)
        else:
            kv_total = _kv_bytes(self._cache)
            metrics.gauge("kv_cache_bytes",
                          "resident KV cache bytes (all slots, max_len)",
                          fn=lambda: _kv_bytes(self._cache))
            metrics.gauge("kv_cache_bytes_per_slot",
                          "resident KV cache bytes per decode slot",
                          fn=lambda: (_kv_bytes(self._cache)
                                      / max(1, self.slots)))
            logger.info("decode KV cache: %d bytes (%d slots x max_len "
                        "%d, %s)", kv_total, self.slots, self.max_len,
                        self.cache_dtype)
            for kind in self.cache_bytes_by_kind():
                metrics.gauge(
                    f"decode_cache_bytes_{kind}",
                    f"resident bytes of the slots' {kind} leaves",
                    fn=lambda kind=kind: self.cache_bytes_by_kind()[kind])
        if self.speculate > 0:
            self._m_spec_prop = metrics.counter(
                "spec_proposed_total", "draft tokens proposed")
            self._m_spec_acc = metrics.counter(
                "spec_accepted_total", "draft tokens accepted by verify")
            self._m_draft_steps = metrics.counter(
                "spec_draft_steps_total", "draft-model decode steps")
            metrics.gauge(
                "spec_accept_rate",
                "accepted / proposed draft tokens",
                fn=lambda: (self._m_spec_acc.value
                            / max(self._m_spec_prop.value, 1)))
            metrics.gauge(
                "spec_accepted_tokens_per_step",
                "tokens emitted per target verify step",
                fn=lambda: (self._m_tokens.value
                            / max(self._m_steps.value, 1)))
        else:
            self._m_spec_prop = self._m_spec_acc = None
            self._m_draft_steps = None

    def kv_bytes(self) -> int:
        """Resident cache bytes — allocated pages when paged, the dense
        slab otherwise, whatever kinds of leaf it holds. Per-replica
        truth; the dp fleet aggregate sums this across replicas (ISSUE 16
        satellite)."""
        if self.paged:
            return self._kv.allocated_bytes()
        from bigdl_tpu.obs.memory import tree_bytes
        return tree_bytes(self._cache)

    def cache_bytes_by_kind(self) -> dict:
        """The dense slab's resident bytes by kind of leaf, as the model
        names them: ``kv_full`` (max_len K/V rows a slot), and for a
        model with window or state-space layers ``kv_window``,
        ``ssm_state``, ``conv_state``. Sums to :meth:`kv_bytes`."""
        return self.model.cache_bytes_by_kind(self._cache)

    def kv_pages_in_use(self) -> int:
        return self._kv.alloc.pages_in_use if self.paged else 0

    def queue_load(self) -> int:
        """Routing signal for dp replica selection: active slots plus
        waiting requests plus submits still being admitted — a prefill
        runs under the engine lock inside ``submit``, and for that long
        (seconds, when the bucket compiles) its request is in neither
        list; uncounted, every concurrent request piled onto one replica.
        Approximate read — routing only needs a consistent ordering, not
        an exact census."""
        return (sum(r is not None for r in self._reqs)
                + len(self._waiting) + self._admitting)

    def _page_occupancy(self) -> float:
        live = int(sum(int(self._pos[i])
                       for i, r in enumerate(self._reqs) if r is not None))
        if self._pfx is not None:
            live += self._pfx.cached_tokens()
        cap = self._kv.alloc.pages_in_use * self.page_tokens
        return live / cap if cap else 0.0

    # ---------------------------------------------------- compiled programs
    def _build_programs(self) -> None:
        jax, jnp = self._jax, self._jnp
        model = self.model
        # donation keeps the big cache in place on device backends; CPU
        # can't honor it and warns on every compile
        self._don = jax.default_backend() != "cpu"

        # tp (ISSUE 16): precompute the sharding pytrees pinned as
        # out_shardings on every program whose output feeds persistent
        # state (_logits / _cache / pools / draft cache) — the layout is
        # decided once here, never re-derived per compile, so sharded
        # state cannot ping-pong between layouts across the lazily-keyed
        # program caches
        shard = self._shard
        if shard is not None:
            cache1_abs = jax.eval_shape(
                lambda: model.init_cache(1, self.max_len,
                                         self.cache_dtype))
            self._cache1_sh = shard.kv_shardings(cache1_abs)
            self._state_sh = (self._kv.pool_shardings if self.paged
                              else shard.kv_shardings(self._cache))
            self._repl_sh = shard.replicated
            self._draft_sh = (shard.kv_shardings(self._draft_cache)
                              if self._draft_cache is not None else None)
        else:
            self._cache1_sh = self._state_sh = self._repl_sh = None
            self._draft_sh = None

        def _prefill(params, tokens, last):
            # tokens (1, bucket) int32; last = true_len - 1 (traced)
            cache = model.init_cache(1, self.max_len,
                                     self.cache_dtype)
            logits, cache = model.prefill_logits(params, tokens, cache,
                                                 last)
            return logits[0].astype(jnp.float32), cache

        self._prefill_fn = _prefill
        self._prefill_jit = jax.jit(  # one compile per bucket
            _prefill, **self._pin(self._repl_sh, self._cache1_sh))

        def _write_slot(cache_full, cache_one, slot):
            return jax.tree_util.tree_map(
                lambda f, o: jax.lax.dynamic_update_index_in_dim(
                    f, o[0].astype(f.dtype), slot, 0),
                cache_full, cache_one)

        self._write_slot = jax.jit(
            _write_slot, donate_argnums=(0,) if self._don else ())
        if self.paged:
            self._scatter_prefill = jax.jit(
                _kvp.scatter_pages,
                donate_argnums=(0,) if self._don else (),
                **self._pin(self._state_sh))
            self._copy_pages_jit = jax.jit(
                _kvp.copy_pages,
                donate_argnums=(0,) if self._don else (),
                **self._pin(self._state_sh))
        # single-vector sampler: install-time first token (speculative)
        self._sample1_jit = jax.jit(
            lambda lg, t, k, p, seed, pos: _spec.sample_token(
                lg, t, k, p, _spec.request_key(seed, pos)))
        # lazily-built program caches, keyed by shape/variant
        self._step_programs: dict = {}
        self._verify_programs: dict = {}
        self._accept_programs: dict = {}
        self._suffix_programs: dict = {}
        self._draft_step_jit = None
        # what the rules of the row write ("batched" | "scatter" a leaf)
        # and of the cache read ("bounded" | "whole" a layer) chose when
        self._step_forms: list = []  # the plain step was traced

    def _pin(self, *out_sh):
        """``out_shardings=`` kwarg for a jit whose outputs must land in
        the tp layout (``{}`` when unsharded — the single-chip programs
        are untouched). Positional order mirrors the program's outputs;
        a single entry pins a single-output program."""
        if self._shard is None:
            return {}
        return {"out_shardings": (out_sh if len(out_sh) > 1
                                  else out_sh[0])}

    def _step_trace(self, keep: bool = False):
        """``ops.cache_write.step_trace`` around a vmapped step's tracing:
        no kernel for a mesh, the whole read over a paged step's pages."""
        from bigdl_tpu.ops.cache_write import step_trace
        if keep:  # /debug/slots shows the plain step traced last
            self._step_forms.clear()
        return step_trace(self._step_forms if keep else None,
                          self._shard is None, not self.paged)

    def _sample_fn(self, warp: bool):
        jax, jnp = self._jax, self._jnp

        def fn(logits, pos, temp, topk, topp, seed):
            key = _spec.request_key(seed, pos)
            if warp:
                return _spec.sample_token(logits, temp, topk, topp, key)
            greedy = jnp.argmax(logits).astype(jnp.int32)
            safe_t = jnp.where(temp > 0, temp, 1.0)
            sampled = jax.random.categorical(
                key, logits / safe_t).astype(jnp.int32)
            return jnp.where(temp > 0, sampled, greedy)

        return fn

    def _get_step(self, warp: bool):
        """The plain per-token step. ``warp=False`` is the sort-free
        program (greedy/temperature-only traffic); ``warp=True`` adds
        the top-k/top-p filters. Both sample identically when the
        filters are disabled, so program choice never changes output."""
        key = ("paged" if self.paged else "dense", warp)
        prog = self._step_programs.get(key)
        if prog is not None:
            return prog
        jax, jnp = self._jax, self._jnp
        model, sample = self.model, self._sample_fn(warp)
        # a model whose step counts on the device: a fourth output
        decode = getattr(model, "decode_logits_stats", model.decode_logits)

        if not self.paged:
            def _one(params, logits, cache1, pos, temp, topk, topp, seed):
                tok = sample(logits, pos, temp, topk, topp, seed)
                cache_b = jax.tree_util.tree_map(lambda a: a[None], cache1)
                with self._step_trace(keep=True):
                    lg, cache_b, *stats = decode(
                        params, tok[None, None], cache_b, pos)
                return (tok, lg[0].astype(jnp.float32),
                        jax.tree_util.tree_map(lambda a: a[0], cache_b),
                        *(st[0] for st in stats))

            prog = jax.jit(
                jax.vmap(_one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0)),
                donate_argnums=(1, 2) if self._don else (),
                **self._pin(self._repl_sh, self._repl_sh,
                            self._state_sh))
        else:
            pt = self.page_tokens

            def _paged_step(params, logits, pools, table, pos, temp,
                            topk, topp, seed):
                def _one(logits, pages, pos, temp, topk, topp, seed):
                    tok = sample(logits, pos, temp, topk, topp, seed)
                    cache1 = _kvp.gather_cache(pools, pages)
                    cache_b = jax.tree_util.tree_map(
                        lambda a: a[None], cache1)
                    with self._step_trace(keep=True):
                        lg, cache_b = model.decode_logits(
                            params, tok[None, None], cache_b, pos)
                    tok_kv = jax.tree_util.tree_map(
                        lambda c: jax.lax.dynamic_slice_in_dim(
                            c[0], pos, 1, axis=1)[:, 0, :], cache_b)
                    return tok, lg[0].astype(jnp.float32), tok_kv

                toks, lgs, tok_kv = jax.vmap(_one)(
                    logits, table, pos, temp, topk, topp, seed)
                page_ids = jnp.take_along_axis(
                    table, (pos // pt)[:, None], axis=1)[:, 0]
                pools2 = _kvp.scatter_tokens(pools, tok_kv, page_ids,
                                             pos % pt)
                return toks, lgs, pools2

            prog = jax.jit(
                _paged_step,
                donate_argnums=(1, 2) if self._don else (),
                **self._pin(self._repl_sh, self._repl_sh,
                            self._state_sh))
        self._step_programs[key] = prog
        return prog

    def _get_draft_step(self):
        if self._draft_step_jit is not None:
            return self._draft_step_jit
        jax, jnp = self._jax, self._jnp
        dmodel = self.draft_model

        def _one(dparams, tok, cache1, pos, temp, topk, topp, seed):
            cache_b = jax.tree_util.tree_map(lambda a: a[None], cache1)
            with self._step_trace():
                lg, cache_b = dmodel.decode_logits(
                    dparams, tok[None, None], cache_b, pos)
            prop, q = _spec.draft_propose(lg[0].astype(jnp.float32),
                                          temp, topk, topp, seed, pos)
            return (prop, q,
                    jax.tree_util.tree_map(lambda a: a[0], cache_b))

        self._draft_step_jit = jax.jit(
            jax.vmap(_one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0)),
            donate_argnums=(2,) if self._don else (),
            **self._pin(self._repl_sh, self._repl_sh, self._draft_sh))
        return self._draft_step_jit

    def _get_verify(self, m: int):
        prog = self._verify_programs.get(m)
        if prog is not None:
            return prog
        jax, jnp = self._jax, self._jnp
        model = self.model

        if not self.paged:
            def _verify(params, toks, cache, pos):
                def _one(toks1, cache1, pos):
                    cache_b = jax.tree_util.tree_map(
                        lambda a: a[None], cache1)
                    lg, cache_b = model.verify_logits(
                        params, toks1[None], cache_b, pos)
                    return (lg[0].astype(jnp.float32),
                            jax.tree_util.tree_map(lambda a: a[0],
                                                   cache_b))

                return jax.vmap(_one, in_axes=(0, 0, 0))(toks, cache, pos)

            prog = jax.jit(_verify,
                           donate_argnums=(2,) if self._don else (),
                           **self._pin(self._repl_sh, self._state_sh))
        else:
            pt = self.page_tokens

            def _verify(params, toks, pools, table, pos):
                def _one(toks1, pages, pos):
                    cache1 = _kvp.gather_cache(pools, pages)
                    cache_b = jax.tree_util.tree_map(
                        lambda a: a[None], cache1)
                    lg, cache_b = model.verify_logits(
                        params, toks1[None], cache_b, pos)
                    tok_kv = jax.tree_util.tree_map(
                        lambda c: jax.lax.dynamic_slice_in_dim(
                            c[0], pos, m, axis=1), cache_b)  # (kh, m, hd)
                    return lg[0].astype(jnp.float32), tok_kv

                lgs, tok_kv = jax.vmap(_one)(toks, table, pos)
                abspos = pos[:, None] + jnp.arange(m)[None, :]  # (S, m)
                page_ids = jnp.take_along_axis(table, abspos // pt,
                                               axis=1).reshape(-1)
                offs = (abspos % pt).reshape(-1)
                flat = jax.tree_util.tree_map(
                    lambda c: c.transpose(0, 2, 1, 3).reshape(
                        (-1,) + c.shape[1:2] + c.shape[3:]), tok_kv)
                pools2 = _kvp.scatter_tokens(pools, flat, page_ids, offs)
                return lgs, pools2

            prog = jax.jit(_verify,
                           donate_argnums=(2,) if self._don else (),
                           **self._pin(self._repl_sh, self._state_sh))
        self._verify_programs[m] = prog
        return prog

    def _get_accept(self, m: int):
        prog = self._accept_programs.get(m)
        if prog is None:
            jax = self._jax
            prog = jax.jit(jax.vmap(_spec.accept_chunk,
                                    in_axes=(0, 0, 0, 0, 0, 0, 0, 0)))
            self._accept_programs[m] = prog
        return prog

    def _get_suffix(self, mb: int):
        """Chunked suffix prefill at a page-aligned offset — the
        prefix-cache HIT path (paged only)."""
        prog = self._suffix_programs.get(mb)
        if prog is not None:
            return prog
        jax, jnp = self._jax, self._jnp
        model = self.model

        def _suffix(params, toks, pages, pos0, last, pools):
            cache1 = _kvp.gather_cache(pools, pages)
            cache_b = jax.tree_util.tree_map(lambda a: a[None], cache1)
            lgs, cache_b = model.verify_logits(params, toks, cache_b,
                                               pos0)
            lg = jax.lax.dynamic_slice_in_dim(
                lgs[0], last, 1, axis=0)[0].astype(jnp.float32)
            pools2 = _kvp.scatter_pages(pools, cache_b, pages)
            return lg, pools2

        prog = jax.jit(_suffix, donate_argnums=(5,) if self._don else (),
                       **self._pin(self._repl_sh, self._state_sh))
        self._suffix_programs[mb] = prog
        return prog

    def trace_step_jaxpr(self):
        """Jaxpr of the full-sampling decode step — what the tpulint
        decode rules inspect (``bigdl_tpu.analysis.run_decode_rules``)."""
        jax, jnp = self._jax, self._jnp
        S, V = self.slots, self.model.vocab
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        sds = lambda a: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)
        args = [sds(self.params), f32(S, V)]
        jnp_u32 = jax.ShapeDtypeStruct((S,), jnp.uint32)
        jax_fn = self._get_step(warp=True)
        if self.paged:
            args += [sds(self._kv.pools),
                     i32(S, self._kv.max_pages), i32(S), f32(S),
                     i32(S), f32(S), jnp_u32]
        else:
            args += [sds(self._cache), i32(S), f32(S), i32(S), f32(S),
                     jnp_u32]
        return jax.make_jaxpr(jax_fn)(*args)

    def prefill_mosaic_kernels(self) -> dict:
        """``{kernel name: [buckets]}`` for the Pallas kernels the traced
        prefill program carries WITHOUT interpret mode — i.e. the ones
        that lower through Mosaic. Empty where prefill attends densely
        or the kernels run interpreted (off-TPU). Trace only: nothing is
        compiled or placed."""
        from bigdl_tpu.analysis.jaxpr_walk import iter_levels
        jax, jnp = self._jax, self._jnp
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        out: dict = {}
        for b in self.prompt_buckets:
            closed = jax.make_jaxpr(self._prefill_fn)(
                abstract, jax.ShapeDtypeStruct((1, b), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
            for lv in iter_levels(closed):
                for eqn in lv.jaxpr.eqns:
                    if (eqn.primitive.name == "pallas_call"
                            and not eqn.params.get("interpret")):
                        name = eqn.params.get("name") or "pallas_call"
                        if b not in out.setdefault(name, []):
                            out[name].append(b)
        return out

    # ------------------------------------------------------------ admission
    def prompt_bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return self.prompt_buckets[-1]

    def submit(self, tokens, max_new_tokens: int,
               temperature: float = 0.0, stop_token=None,
               deadline: Optional[float] = None, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0,
               rid: Optional[str] = None, emit=None) -> _Future:
        """Queue one generation request; the future resolves to the list
        of generated token ids. Validates the length budget, fast-rejects
        when the waiting queue is full, when the decode worker is dead
        (:class:`WorkerDied` — nothing would ever drain the queue), or
        when ``deadline`` (absolute, on the engine's clock) has already
        passed (:class:`DeadlineExceeded`). ``top_k=0`` / ``top_p=1``
        disable those filters; ``seed`` makes sampled output
        deterministic per request; ``rid`` tags the request for
        lifecycle tracing (ISSUE 15); ``emit`` is an optional streaming
        sink called as ``emit(new_tokens, done)`` per emitting round
        (ISSUE 18) — called under the engine lock, so it must only hand
        tokens off (e.g. queue.put), never block."""
        tokens = list(tokens)
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if len(tokens) + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({len(tokens)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.max_len}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        req = DecodeRequest(tokens, max_new_tokens, temperature,
                            stop_token, deadline, top_k, top_p, seed,
                            rid=rid, emit=emit)
        with self._admit_lock:
            self._admitting += 1
        try:
            self._admit(req, deadline, rid)
        finally:
            with self._admit_lock:
                self._admitting -= 1
        return req.future

    @contextlib.contextmanager
    def _locked(self, wait_span: str, **args):
        """Hold the engine lock; the wait for it is the span."""
        with _obs_span(wait_span, **args):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _admit(self, req, deadline, rid) -> None:
        with self._locked("submit_lock_wait", rid=rid):
            if not self.busy():
                # work arrives at an idle engine: the stall clock starts
                # NOW, not at the loop's last (arbitrarily old) idle beat
                self._last_beat = self.clock()
            if self._closed:
                raise RuntimeError("decode engine is closed")
            if self._worker_error is not None or (
                    self._thread is not None
                    and not self._thread.is_alive()):
                if self._m_dead is not None:
                    self._m_dead.inc()
                raise WorkerDied(
                    "decode worker is dead: "
                    f"{self._worker_error or 'thread exited'}")
            if deadline is not None and self.clock() >= deadline:
                if self._m_expired is not None:
                    self._m_expired.inc()
                raise DeadlineExceeded("deadline expired before submit")
            slot = self._free_slot()
            if slot is not None and self._install(req, slot):
                pass
            elif len(self._waiting) >= self.max_waiting:
                if self._m_rejected is not None:
                    self._m_rejected.inc()
                raise AdmissionError(
                    f"decode queue at capacity ({self.max_waiting} waiting)")
            else:
                req.t_queued = self.clock()
                self._waiting.append(req)
                if rid is not None:
                    rt = _get_reqtracer()
                    if rt is not None:
                        rt.note_queued(rid)
            self._work.notify()

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self._reqs):
            if r is None:
                return i
        return None

    def _release_slot(self, slot: int) -> None:
        self._reqs[slot] = None
        self._pos[slot] = 0
        if self.paged:
            self._kv.release(slot)

    def _handoff(self, slot: int) -> None:
        """Install the next waiting request into a freed slot. A paged
        reservation failure (pool still too full) puts the request back
        at the queue head — FIFO order is preserved and the request is
        retried as soon as more pages free up."""
        while self._waiting:
            req = self._waiting.popleft()
            if self._install(req, slot):
                if self._m_queued is not None:
                    self._m_queued.inc()
                    self._m_queue_wait.inc(self.clock() - req.t_queued)
                return
            self._waiting.appendleft(req)
            return

    # -------------------------------------------------------------- prefill
    def _install(self, req: DecodeRequest, slot: int) -> bool:
        """Prefill ``req``'s prompt into ``slot`` (lock held). False iff
        the paged pool cannot serve the request's page reservation yet —
        the caller keeps it queued; nothing was spent."""
        jnp = self._jnp
        s = len(req.tokens)
        if self.paged and not self._kv.reserve(slot,
                                               s + req.max_new_tokens):
            return False
        rt = _get_reqtracer() if req.rid is not None else None
        t0_pf = rt.clock() if rt is not None else 0.0
        n_pfx, src_pages = (self._pfx.match(req.tokens)
                            if self._pfx is not None else (0, []))
        # a prefix hit prefills the suffix alone, at its own bucket
        bucket = (min(self.prompt_bucket_for(s - n_pfx),
                      self.max_len - n_pfx)
                  if n_pfx else self.prompt_bucket_for(s))
        with _obs_span("decode_prefill", self._round_calls, prompt=s,
                       rid=req.rid, bucket=bucket, slot=slot):
            if n_pfx:
                logits_vec = self._prefill_from_prefix(
                    req, slot, n_pfx, src_pages, bucket)
            else:
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :s] = req.tokens
                # the prompt's length in the span's name: a reader of a
                # profiler session sees names only, and the operations a
                # prefill needs go by its real tokens, not its bucket's
                with _obs_span(f"prefill_tokens_{s}"):
                    logits_vec, cache1 = self._prefill_jit(
                        self.params, jnp.asarray(padded), jnp.int32(s - 1))
                if self.paged:
                    self._kv.pools = self._scatter_prefill(
                        self._kv.pools, cache1,
                        jnp.asarray(self._kv.page_table[slot]))
                else:
                    self._cache = self._write_slot(self._cache, cache1,
                                                   jnp.int32(slot))
            if self._pfx is not None:
                self._maybe_insert_prefix(req, slot)
        self._logits = self._logits.at[slot].set(logits_vec)
        # a completed prefill IS progress: installs run under the engine
        # lock (on the submitter's thread), so while one compiles its
        # bucket the loop cannot beat — without this the watchdog read a
        # second install behind a busy slot as a wedged loop
        self._last_beat = self.clock()
        self._pos[slot] = s
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._seed[slot] = req.seed
        self._reqs[slot] = req
        if self._m_prefills is not None:
            self._m_prefills.inc()
            self._m_prompt_tokens.inc(s - n_pfx)
            self._m_bucket_tokens.inc(bucket)
            if bucket > (getattr(self.model, "window", None) or bucket):
                self._m_window_tokens.inc(s)
        if rt is not None:
            rt.note_prefill(
                req.rid, t0_pf, rt.clock(), slot=slot,
                prefix_hit_tokens=n_pfx,
                pages=(len(self._kv.slot_pages[slot])
                       if self.paged else None))
        if self.speculate > 0:
            self._install_draft(req, slot)
            # speculative mode emits the first token NOW (it becomes the
            # round's pending feed) — same sample the plain step's first
            # iteration would draw (same key: fold_in(seed, pos=s))
            tok0 = int(self._sample1_jit(
                logits_vec, jnp.float32(req.temperature),
                jnp.int32(req.top_k), jnp.float32(req.top_p),
                jnp.uint32(req.seed), jnp.int32(s)))
            self._pending[slot] = tok0
            self._emit(req, slot, [tok0])
        return True

    def _prefill_from_prefix(self, req, slot: int, n_pfx: int, src_pages,
                             mb: int):
        """Prefix-cache HIT: device-copy the entry's pages into the
        slot, then chunk-prefill only the suffix at offset ``n_pfx``,
        padded to ``mb`` — bit-identical to the full prefill (the copied
        K/V came from the identical graph; suffix rows compute the same
        per-row math)."""
        jnp = self._jnp
        pt = self.page_tokens
        dst = self._kv.page_table[slot, :n_pfx // pt]
        with _obs_span("prefix_copy", pages=len(src_pages)):
            self._kv.pools = self._copy_pages_jit(
                self._kv.pools, jnp.asarray(src_pages, jnp.int32),
                jnp.asarray(dst))
        suffix = req.tokens[n_pfx:]
        padded = np.zeros((1, mb), np.int32)
        padded[0, :len(suffix)] = suffix
        logits_vec, self._kv.pools = self._get_suffix(mb)(
            self.params, jnp.asarray(padded),
            jnp.asarray(self._kv.page_table[slot]), jnp.int32(n_pfx),
            jnp.int32(len(suffix) - 1), self._kv.pools)
        return logits_vec

    def _maybe_insert_prefix(self, req, slot: int) -> None:
        ins = self._pfx.prepare_insert(req.tokens)
        if ins is None:
            return
        key, dst_pages = ins
        need = len(dst_pages)
        src = self._kv.page_table[slot, :need]
        jnp = self._jnp
        self._kv.pools = self._copy_pages_jit(
            self._kv.pools, jnp.asarray(src),
            jnp.asarray(dst_pages, jnp.int32))
        self._pfx.commit_insert(key, dst_pages, need * self.page_tokens)

    def _install_draft(self, req, slot: int) -> None:
        """Prefill the draft model's own (dense) cache for this slot."""
        jax, jnp = self._jax, self._jnp
        s = len(req.tokens)
        bucket = self.prompt_bucket_for(s)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :s] = req.tokens
        if not hasattr(self, "_draft_prefill_jit"):
            dmodel, ddtype = self.draft_model, self._draft_dtype

            def _dprefill(dparams, tokens, last):
                cache = dmodel.init_cache(1, self.max_len, ddtype)
                _, cache = dmodel.prefill_logits(dparams, tokens, cache,
                                                 last)
                return cache

            pin = {}
            if self._shard is not None:
                dcache1_abs = jax.eval_shape(
                    lambda: dmodel.init_cache(1, self.max_len, ddtype))
                pin = self._pin(self._shard.kv_shardings(dcache1_abs))
            self._draft_prefill_jit = jax.jit(_dprefill, **pin)
        cache1 = self._draft_prefill_jit(
            self.draft_params, jnp.asarray(padded), jnp.int32(s - 1))
        self._draft_cache = self._write_slot(self._draft_cache, cache1,
                                             jnp.int32(slot))

    # ------------------------------------------------------------- emission
    def _emit(self, req, slot: int, toks, accepted=None, pos=None) -> bool:
        """Append generated tokens to ``req`` (respecting stop token and
        max_new budget), resolve + hand off if finished. ``accepted`` is
        the speculative draft tokens the verify kept this round (None on
        the plain path); ``pos`` the slot's position after these tokens,
        where the host's own may already be a step further (default: the
        host's). Returns True if the request completed. Lock held."""
        done = False
        emitted = 0
        for tok in toks:
            req.out.append(int(tok))
            emitted += 1
            if (len(req.out) >= req.max_new_tokens
                    or (req.stop_token is not None
                        and int(tok) == req.stop_token)):
                done = True
                break
        if self._m_tokens is not None and emitted:
            self._m_tokens.inc(emitted)
        rt = _get_reqtracer() if req.rid is not None else None
        if rt is not None and emitted:
            rt.note_round(
                req.rid, emitted, accepted=accepted,
                pages=(len(self._kv.slot_pages[slot])
                       if self.paged else None),
                pos=int(self._pos[slot] if pos is None else pos))
        if req.emit is not None and emitted:
            # streaming sink (ISSUE 18): hand the round's accepted
            # tokens to the HTTP handler's queue. A broken sink must
            # never take the decode loop (and every other slot) down —
            # the disconnect path is decoder.cancel(), not an exception
            # propagated from here.
            try:
                req.emit(req.out[-emitted:], done)
            except Exception:
                logger.exception("streaming emit sink failed (rid=%s)",
                                 req.rid)
        if done:
            self._release_slot(slot)
            req.future.set_result(list(req.out))
            if rt is not None:
                rt.finish(req.rid, "finished")
            self._handoff(slot)
        return done

    # ------------------------------------------------------------- deadlines
    def _expire(self, now: float) -> None:
        """Drop expired requests BEFORE compute is spent on them (lock
        held): waiting-queue entries simply resolve with
        :class:`DeadlineExceeded`; active slots free up and hand off to
        the next (still-live) waiting request."""
        rt = _get_reqtracer()
        if self._waiting:
            live = collections.deque()
            for req in self._waiting:
                if req.deadline is not None and now >= req.deadline:
                    if self._m_expired is not None:
                        self._m_expired.inc()
                    req.future.set_exception(DeadlineExceeded(
                        "deadline expired while waiting for a decode "
                        "slot"))
                    if rt is not None and req.rid is not None:
                        rt.finish(req.rid, "expired",
                                  error="expired in decode queue")
                else:
                    live.append(req)
            self._waiting = live
        for i, req in enumerate(self._reqs):
            if (req is not None and req.deadline is not None
                    and now >= req.deadline):
                self._release_slot(i)
                if self._m_expired is not None:
                    self._m_expired.inc()
                req.future.set_exception(DeadlineExceeded(
                    f"deadline expired after {len(req.out)} of "
                    f"{req.max_new_tokens} tokens"))
                if rt is not None and req.rid is not None:
                    rt.finish(req.rid, "expired",
                              error=f"expired mid-decode after "
                                    f"{len(req.out)} tokens")
                self._handoff(i)

    # --------------------------------------------------------- cancellation
    def cancel(self, rid: str, reason: str = "client disconnected") -> bool:
        """First-class mid-decode cancellation (ISSUE 18): drop the
        request identified by ``rid`` wherever it is — waiting queue or
        active slot — releasing the slot AND its paged-KV page
        reservation atomically under the engine lock, then hand the slot
        to the next waiting request. This is the primitive the streaming
        disconnect path uses (previously only deadline expiry and
        shutdown freed slots early).

        Returns True iff a request was found and cancelled. Safe against
        the speculative verify/accept race: a round holds the engine
        lock from its first dispatch to its last emission (draft feeds,
        the chunked verify dispatch, acceptance, and emission), so a
        cancel landing between a verify dispatch and its accept simply
        waits for the round to retire — it can never free pages the
        in-flight verify is still writing, and a stale ``_pending`` feed
        is reset by the next ``_install`` into that slot. A plain step
        the decode loop has in flight is another matter: the cancel lands
        between two rounds while the device still runs (or has queued) a
        step that advanced this slot. Nothing waits for it. The slot is
        freed and handed on at once; the step's write goes to a row the
        next owner's prefill, queued behind it on the device, overwrites
        (dense: the whole slot; paged: the freed page, or the null page),
        and its token is dropped at retire because the slot no longer
        holds the request it was computed for."""
        if rid is None:
            return False
        err = RuntimeError(f"request {rid} cancelled: {reason}")
        rt = _get_reqtracer()
        with self._lock:
            for req in self._waiting:
                if req.rid == rid:
                    self._waiting.remove(req)
                    break
            else:
                req = None
            if req is None:
                for i, r in enumerate(self._reqs):
                    if r is not None and r.rid == rid:
                        req = r
                        self._release_slot(i)
                        self._handoff(i)
                        break
            if req is None:
                return False
            if self._m_cancelled is not None:
                self._m_cancelled.inc()
            self._work.notify()
        req.future.set_exception(err)
        if rt is not None:
            rt.finish(rid, "closed", error=reason)
        return True

    # ---------------------------------------------------------------- step
    def step(self) -> int:
        """One batched decode step: every active slot emits one token
        (plain) or up to ``speculate+1`` tokens (speculative round).
        Returns the number of active slots (0 = idle). Finished requests
        resolve their futures and hand their slot to the next waiting
        request; expired ones are dropped before compute. One step deep:
        the step it dispatches is the step it retires, and nothing is in
        flight when it returns."""
        return self._round(ahead=False)

    def _round(self, ahead: bool) -> int:
        """One round under the engine lock. ``ahead``: leave the plain
        step this round dispatches in flight and retire the one the round
        before left (the decode loop); else retire what it dispatched."""
        with self._locked("decode_lock_wait"):
            self._last_beat = self.clock()
            self._expire(self.clock())
            active = [i for i, r in enumerate(self._reqs)
                      if r is not None]
            if active:
                if self._m_rounds is not None:
                    self._m_rounds.inc()
                with _obs_span("decode_round", self._c_round,
                               active=len(active)):
                    self._round_calls = self._c_call  # a hand-off's prefill
                    try:
                        if self.speculate > 0:
                            return self._step_spec(active)
                        self._step_plain(active, ahead)
                    finally:
                        self._round_calls = None
            if self._flight is not None and not any(
                    r is not None for r in self._reqs):
                # every request the step in flight advanced has ended
                self._drop_flight()
            return len(active)

    def _sampling_args(self, pos=None):
        jnp = self._jnp
        return (jnp.asarray(self._pos if pos is None else pos),
                jnp.asarray(self._temp),
                jnp.asarray(self._topk), jnp.asarray(self._topp),
                jnp.asarray(self._seed))

    def _needs_warp(self, active) -> bool:
        return any(self._topk[i] > 0 or self._topp[i] < 1.0
                   for i in active)

    def _count_step(self, active) -> None:
        # at dispatch, before the positions advance
        if self._m_steps is not None:
            self._m_steps.inc()
            self._m_live_pos.inc(int(self._pos[active].sum()))
            window = getattr(self.model, "window", None)
            if window:
                self._m_window_pos.inc(
                    int(np.minimum(self._pos[active], window).sum()))

    def _step_plain(self, active, ahead: bool) -> None:
        """Dispatch a step, then retire one: the same step, or with
        ``ahead`` the step the round before left in flight, so that the
        device runs this round's step while the host emits the last
        one's tokens."""
        with _obs_span("decode_args"):
            # a slot whose budget the token in flight already covers is
            # run like a free slot; the host knows without reading
            unread = ({slot for slot, req, _ in self._flight.advanced
                       if self._reqs[slot] is req}
                      if self._flight is not None else ())
            advance, held = [], []
            for i in active:
                req = self._reqs[i]
                (advance if len(req.out) + (i in unread)
                 < req.max_new_tokens else held).append(i)
            args = self._step_args(advance, held) if advance else None
        with _obs_span("decode_step", active=len(advance)):
            flight = self._dispatch(advance, *args) if advance else None
            if ahead:
                flight, self._flight = self._flight, flight
            if flight is None:  # the loop's first round after idling
                return
            with _obs_span("decode_host_read", self._c_read):
                if flight.stats is None:
                    toks_host = np.asarray(flight.toks)
                else:  # the step's counts ride with its tokens: one wait
                    toks_host, stats_host = self._jax.device_get(
                        (flight.toks, flight.stats))
        with _obs_span("decode_emit"):
            self._retire(flight, toks_host)
            if flight.stats is not None and self._m_model:
                live = [slot for slot, _, _ in flight.advanced]
                for name, n in self.model.count_step(
                        stats_host[live]).items():
                    self._m_model[name].inc(n)

    def _step_args(self, advance, held):
        """The program and the five sampling arrays of a step that
        advances ``advance``. A live slot that is not advanced (``held``)
        runs at its last position, a free one at 0: neither is ever run
        past its ``prompt + max_new - 1``."""
        pos = self._pos.copy()
        pos[held] -= 1
        return (self._get_step(self._needs_warp(advance)),
                *self._sampling_args(pos))

    def _dispatch(self, advance, prog, pos, temp, topk, topp,
                  seed) -> _Flight:
        """Hand the device one plain step and advance the host's
        positions; the host reads nothing."""
        jnp = self._jnp
        try:
            stats = ()
            with _obs_span("decode_call", self._c_call):
                if self.paged:
                    toks, self._logits, self._kv.pools = prog(
                        self.params, self._logits, self._kv.pools,
                        jnp.asarray(self._kv.page_table), pos, temp,
                        topk, topp, seed)
                else:
                    toks, self._logits, self._cache, *stats = prog(
                        self.params, self._logits, self._cache, pos,
                        temp, topk, topp, seed)
        except Exception as e:
            # RESOURCE_EXHAUSTED autopsy (ISSUE 12): the KV cache is
            # usually the culprit — report to --traceDir + fault
            # log, then die as before
            from bigdl_tpu.obs import memory as _obs_mem
            _obs_mem.handle_oom(e, "decode_step")
            raise
        self._count_step(advance)
        if self._flight is not None and self._m_runahead is not None:
            self._m_runahead.inc()
        advanced = [(i, self._reqs[i], int(self._pos[i])) for i in advance]
        self._pos[advance] += 1
        return _Flight(toks, advanced, stats[0] if stats else None)

    def _retire(self, flight: _Flight, toks_host) -> None:
        """Emit a dispatched step's tokens, each to the request it was
        computed for if the slot still holds it."""
        for slot, req, pos in flight.advanced:
            if self._reqs[slot] is req:
                self._emit(req, slot, [int(toks_host[slot])], pos=pos + 1)
            elif self._m_dropped is not None:
                self._m_dropped.inc()

    def _drop_flight(self) -> None:
        """Forget the step in flight without reading it (lock held):
        its tokens are dropped and counted, and what it writes goes where
        any slot's next owner overwrites."""
        flight, self._flight = self._flight, None
        if flight is not None and self._m_dropped is not None:
            self._m_dropped.inc(len(flight.advanced))

    def _step_spec(self, active) -> int:
        """One speculative round: m-1 draft proposals + the sync feed,
        ONE chunked target verify, exact acceptance, emit 1..m tokens
        per slot (m = speculate+1 clamped to the cache tail)."""
        jax, jnp = self._jax, self._jnp
        # the chunk writes K/V at pos..pos+m-1 for every active slot;
        # clamping m keeps writes inside max_len (dynamic_update_slice
        # would silently SHIFT an out-of-range window). pos <= max_len-2
        # always (prompt+max_new <= max_len and the final token is never
        # fed), so m >= 2 — at least one proposal per round.
        m = min(self.speculate + 1,
                self.max_len - max(int(self._pos[i]) for i in active))
        with _obs_span("decode_args"):
            pos, temp, topk, topp, seed = self._sampling_args()
            feed = jnp.asarray(self._pending)
            draft_step = self._get_draft_step()
        props, qrows = [], []
        with _obs_span("spec_draft", self._c_call, active=len(active),
                       feeds=m):
            for j in range(m):
                prop_j, q_j, self._draft_cache = draft_step(
                    self.draft_params, feed, self._draft_cache,
                    pos + j, temp, topk, topp, seed)
                if j < m - 1:
                    props.append(prop_j)
                    qrows.append(q_j)
                    feed = prop_j
        if self._m_draft_steps is not None:
            self._m_draft_steps.inc(m * len(active))
        chunk = jnp.stack([jnp.asarray(self._pending)] + props, axis=1)
        if props:
            pstack = jnp.stack(props, axis=1)
            qstack = jnp.stack(qrows, axis=1)
        else:
            # m == 1 (a slot is one token from max_len): pure verify of
            # the pending feed, zero proposals — accept_chunk handles
            # the degenerate (m-1)=0 shapes
            pstack = jnp.zeros((self.slots, 0), jnp.int32)
            qstack = jnp.zeros((self.slots, 0, self.model.vocab),
                               jnp.float32)
        with _obs_span("spec_verify", self._c_call, active=len(active),
                       chunk=m):
            try:
                if self.paged:
                    T, self._kv.pools = self._get_verify(m)(
                        self.params, chunk, self._kv.pools,
                        jnp.asarray(self._kv.page_table), pos)
                else:
                    T, self._cache = self._get_verify(m)(
                        self.params, chunk, self._cache, pos)
            except Exception as e:
                from bigdl_tpu.obs import memory as _obs_mem
                _obs_mem.handle_oom(e, "decode_step")
                raise
        emitted, n_emit, n_acc = self._get_accept(m)(
            T, qstack, pstack, temp, topk, topp, seed, pos)
        with _obs_span("decode_host_read", self._c_read):
            emitted = np.asarray(emitted)
            n_emit = np.asarray(n_emit)
            n_acc = np.asarray(n_acc)
        self._count_step(active)
        if self._m_spec_prop is not None:
            self._m_spec_prop.inc((m - 1) * len(active))
            self._m_spec_acc.inc(int(sum(int(n_acc[i]) for i in active)))
        with _obs_span("decode_emit"):
            for i in active:
                req = self._reqs[i]
                k = int(n_emit[i])
                stream = [int(t) for t in emitted[i, :k]]
                self._pos[i] += k
                if not self._emit(req, i, stream, accepted=int(n_acc[i])):
                    self._pending[i] = stream[-1]
        return len(active)

    def generate(self, tokens, max_new_tokens: int,
                 temperature: float = 0.0, stop_token=None, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0) -> list:
        """Synchronous single-request convenience: submit + drive the
        decode loop until this request resolves (other queued requests
        keep advancing alongside — continuous batching has no 'exclusive'
        mode)."""
        fut = self.submit(tokens, max_new_tokens, temperature, stop_token,
                          top_k=top_k, top_p=top_p, seed=seed)
        if self._thread is None:
            while not fut.done():
                if self.step() == 0 and not fut.done():
                    raise RuntimeError(
                        "decode engine idle with unresolved request")
        return fut.result()

    # ----------------------------------------------------- debug inspection
    def debug_snapshot(self) -> dict:
        """The /debug/slots JSON (ISSUE 15): the slot table, waiting
        queue depth, and — paged — the KV page-pool occupancy. Holds the
        engine lock only to copy a few scalars."""
        with self._lock:
            slots = []
            for i, req in enumerate(self._reqs):
                if req is None:
                    slots.append({"slot": i, "state": "free"})
                    continue
                slots.append({
                    "slot": i, "state": "active",
                    "rid": req.rid,
                    "pos": int(self._pos[i]),
                    "prompt_tokens": len(req.tokens),
                    "tokens_out": len(req.out),
                    "max_new": req.max_new_tokens,
                    "pages": (len(self._kv.slot_pages[i])
                              if self.paged else None)})
            out = {"slots": slots,
                   "slots_total": self.slots,
                   "slots_active": sum(1 for r in self._reqs
                                       if r is not None),
                   "waiting": len(self._waiting),
                   "max_waiting": self.max_waiting,
                   "speculate": self.speculate,
                   "worker_up": self._worker_error is None,
                   "tp": self._shard.n_shard if self._shard else 1,
                   "kv": {"paged": self.paged}}
            if self._step_forms:  # known once the step has been traced
                out["kv"]["row_write"] = (
                    "batched" if "scatter" not in self._step_forms
                    else "scatter")
                out["kv"]["cache_read"] = [
                    f for f in self._step_forms if f in ("bounded", "whole")]
            if not self.paged:
                out["kv"]["bytes_by_kind"] = self.cache_bytes_by_kind()
            else:
                out["kv"].update({
                    "page_tokens": self.page_tokens,
                    "pool_pages": self._kv.pool_pages,
                    "pages_in_use": self._kv.alloc.pages_in_use,
                    "free_pages": self._kv.alloc.free_pages,
                    "occupancy_frac": round(self._page_occupancy(), 4),
                    "allocated_bytes": self._kv.allocated_bytes(),
                    "bytes_per_page": self._kv.bytes_per_page})
        return out

    # ------------------------------------------------------ watchdog surface
    def alive(self) -> bool:
        """False once the decode loop has died or been declared dead
        (threadless caller-driven mode counts as alive)."""
        if self._worker_error is not None:
            return False
        return self._thread is None or self._thread.is_alive()

    def busy(self) -> bool:
        """True while there is work a healthy decode loop should be
        advancing (active slots or waiting requests)."""
        return (any(r is not None for r in self._reqs)
                or bool(self._waiting))

    def heartbeat_age(self, now: Optional[float] = None) -> float:
        return (self.clock() if now is None else now) - self._last_beat

    @property
    def worker_error(self) -> Optional[BaseException]:
        return self._worker_error

    def declare_dead(self, exc: BaseException) -> None:
        """Fail every in-flight and waiting request with
        :class:`WorkerDied` and make subsequent submits fast-fail —
        the watchdog's verdict on a wedged loop, or the loop's own."""
        with self._lock:
            if self._worker_error is None:
                self._worker_error = exc
            dead = list(self._waiting)
            self._waiting.clear()
            for i, req in enumerate(self._reqs):
                if req is not None:
                    self._release_slot(i)
                    dead.append(req)
            self._drop_flight()
            self._work.notify_all()
        err = (exc if isinstance(exc, WorkerDied)
               else WorkerDied(f"decode worker died: {exc}"))
        rt = _get_reqtracer()
        for req in dead:
            req.future.set_exception(err)
            if rt is not None and req.rid is not None:
                rt.finish(req.rid, "worker_dead", error=str(err))

    # --------------------------------------------------------------- worker
    def start(self) -> None:
        """Launch the decode loop thread (server mode)."""
        if self._thread is not None:
            return

        def _loop():
            try:
                while True:
                    with self._locked("decode_lock_wait"):
                        self._last_beat = self.clock()
                        while (not self._closed
                               and not any(r is not None
                                           for r in self._reqs)):
                            with _obs_span("decode_idle"):
                                self._work.wait()
                            self._last_beat = self.clock()
                        if self._closed:
                            return
                    # one plain step ahead of the emit; a speculative
                    # round needs its accepted counts before the next
                    self._round(ahead=self.speculate == 0)
            except BaseException as e:
                # the loop is the only thing advancing decode: record
                # the cause, fail every waiter, fast-fail future submits
                self.declare_dead(e)

        self._thread = threading.Thread(target=_loop, name="decode-loop",
                                        daemon=True)
        self._thread.start()

    def close(self, timeout: float = 10.0) -> None:
        rt = _get_reqtracer()
        with self._lock:
            self._closed = True
            for req in list(self._waiting):
                req.future.set_exception(
                    RuntimeError("decode engine closed"))
                if rt is not None and req.rid is not None:
                    rt.finish(req.rid, "closed")
            self._waiting.clear()
            for i, req in enumerate(self._reqs):
                if req is not None:
                    self._release_slot(i)
                    req.future.set_exception(
                        RuntimeError("decode engine closed mid-request"))
                    if rt is not None and req.rid is not None:
                        rt.finish(req.rid, "closed")
            self._drop_flight()
            self._work.notify_all()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)


# ------------------------------------------------- shardlint (ISSUE 19)
class _AbstractPagedKv:
    """Just enough of :class:`~bigdl_tpu.serving.kv_pages.PagedKvCache`
    for :meth:`DecodeEngine.trace_step_jaxpr`: abstract pools (the same
    leaf geometry the real pool allocates, as ShapeDtypeStructs) plus
    the page-table bound — no allocator, no device memory."""

    def __init__(self, pools, max_pages: int, pool_pages: int,
                 page_tokens: int):
        self.pools = pools
        self.max_pages = int(max_pages)
        self.pool_pages = int(pool_pages)
        self.page_tokens = int(page_tokens)
        self.pool_shardings = None


def abstract_decode_engine(model, *, slots: int = 4,
                           max_len: Optional[int] = None,
                           cache_dtype=None,
                           kv_page_tokens: Optional[int] = None,
                           pool_pages: Optional[int] = None,
                           speculate: int = 0, tp: int = 1,
                           model_axis: str = "model",
                           quantize: Optional[str] = None):
    """A lintable :class:`DecodeEngine` shell: every field
    ``trace_step_jaxpr`` (and the ``_get_step`` program builder under
    it) reads, built fully abstractly — params/KV from ``eval_shape``,
    the tp mesh an :class:`jax.sharding.AbstractMesh`, nothing placed,
    nothing compiled, zero devices required (ISSUE 19: the serving
    surfaces shardlint analyzes without standing up an engine).

    Returns the engine shell; call ``trace_step_jaxpr()`` on it. Do NOT
    ``start()``/``submit()`` it — there is no worker, no allocator, and
    no real state behind it."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.serving import quant as _q

    eng = DecodeEngine.__new__(DecodeEngine)
    eng.model = model
    eng._jax, eng._jnp = jax, jnp
    eng.quantize = quantize if quantize else "off"
    eng._wfmt, eng._kv8 = _q.parse_quantize(quantize)
    eng.slots = int(slots)
    eng.max_len = int(max_len or model.max_len)
    eng.cache_dtype = cache_dtype or model.compute_dtype or jnp.float32
    eng.speculate = int(speculate)
    eng.page_tokens = int(kv_page_tokens) if kv_page_tokens else None
    eng.paged = eng.page_tokens is not None
    if eng._kv8 and not eng.paged:
        raise ValueError("--quantize kv8 needs paged KV "
                         "(--kvPageTokens); the dense cache path has no "
                         "quantized pools")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if eng._wfmt is not None:
        params = jax.eval_shape(
            lambda p: _q.quantize_params(p, eng._wfmt), params)
    eng.params = params

    if int(tp) > 1:
        from jax.sharding import AbstractMesh

        from bigdl_tpu.serving.sharding import ServingSharding
        eng.mesh = AbstractMesh((int(tp),), (model_axis,))
        eng._shard = ServingSharding(eng.mesh, axis=model_axis)
    else:
        eng.mesh = None
        eng._shard = None

    if eng.paged:
        if eng.max_len % eng.page_tokens:
            raise ValueError(
                f"kv page_tokens ({eng.page_tokens}) must divide "
                f"max_len ({eng.max_len})")
        max_pages = eng.max_len // eng.page_tokens
        pp = int(pool_pages or (1 + eng.slots * max_pages))
        tmpl = jax.eval_shape(
            lambda: model.encoder.init_cache(1, eng.page_tokens,
                                             eng.cache_dtype))
        if eng._kv8:
            def mk(a):
                kh, pt, hd = a.shape[1], a.shape[2], a.shape[3]
                return _kvp.QuantPool(
                    jax.ShapeDtypeStruct((pp, kh, pt, hd), jnp.int8),
                    jax.ShapeDtypeStruct((pp, kh, pt), jnp.float32),
                    eng.cache_dtype)
            pools = jax.tree_util.tree_map(mk, tmpl)
        else:
            pools = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct((pp,) + a.shape[1:],
                                               a.dtype), tmpl)
        eng._kv = _AbstractPagedKv(pools, max_pages, pp, eng.page_tokens)
        eng._cache = None
    else:
        eng._kv = None
        eng._cache = jax.eval_shape(
            lambda: model.init_cache(eng.slots, eng.max_len,
                                     eng.cache_dtype))

    shard = eng._shard
    if shard is not None:
        eng._repl_sh = shard.replicated
        eng._state_sh = shard.kv_shardings(
            eng._kv.pools if eng.paged else eng._cache)
    else:
        eng._repl_sh = eng._state_sh = None
    eng._cache1_sh = eng._draft_sh = None
    eng._don = False           # nothing real to donate; CPU-safe
    eng._step_programs = {}
    eng._verify_programs = {}
    eng._accept_programs = {}
    eng._suffix_programs = {}
    eng._draft_step_jit = None
    eng._step_forms = []
    return eng
