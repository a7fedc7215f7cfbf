"""Stdlib HTTP serving surface: JSON in, JSON out, no new dependencies.

BigDL 2.0's Cluster Serving put a full streaming stack (Redis + Flink)
in front of the model; the TPU-native equivalent starts smaller and
honest: a ``ThreadingHTTPServer`` (one thread per connection, fine at
micro-batcher concurrency levels; a streamed ``/generate``'s thread
admits the request, sends the headers and then sleeps until its stream
ends, while one stream writer thread an app sends every stream's SSE
frames, a decode round's frames in one pass) exposing

* ``POST /predict``  — ``{"inputs": [...]}`` -> argmax predictions
  (scores on request), routed through the dynamic micro-batcher so
  concurrent callers share bucketed forwards;
* ``POST /generate`` — ``{"tokens": [...], "max_new_tokens": N}`` ->
  generated token ids from the continuous-batching KV-cache decoder
  (LM models only); optional ``temperature`` / ``top_k`` / ``top_p`` /
  ``seed`` select and seed the sampling mode (per-request counter-based
  randomness: the same seed replays the same output);
* ``GET /healthz``   — LIVENESS: 200 while the process can answer HTTP
  at all (a degraded server is alive — restarting it would lose the
  still-working endpoints);
* ``GET /readyz``    — READINESS: 200 only while every worker is
  healthy and no deliberate overload shed is active — the signal a load
  balancer drains on;
* ``GET /metrics``   — plaintext counters/histograms with the serving
  config provenance stamped into every scrape;
* ``GET /debug/requests`` / ``GET /debug/slots`` — the flight recorder
  (in-flight + recent request lifecycle records; 404 with ``--reqTrace
  off``) and the decoder slot table / KV page-pool occupancy (ISSUE 15).

Every response echoes ``x-request-id`` (client-supplied id wins, else
one is minted) so callers can join server-side lifecycle records and
access-log lines to their own request logs.

Error contract: malformed JSON/fields -> 400, admission rejection or
overload shed (queue full / tiered degradation) -> 429 with
``Retry-After``, request deadline expired before compute -> 504, dead
or wedged worker -> 503 (fast, via the watchdog — not after the
client's timeout), engine failure -> 500; every error body is
``{"error": ...}``.

Graceful degradation is TIERED: under overload the server sheds
``/generate`` first (decode holds slots for seconds; one shed frees
real capacity) while ``/predict`` — cheap, micro-batched — keeps
admitting until its own queue limit; ``/healthz`` stays green
throughout so the process is drained, not killed.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import selectors
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from bigdl_tpu.obs.spans import span as _obs_span
from bigdl_tpu.resilience.faults import TransientFault, hook as _fault_hook
from bigdl_tpu.serving import reqtrace as _reqtrace
from bigdl_tpu.serving.batcher import (AdmissionError, DeadlineExceeded,
                                       WorkerDied)

logger = logging.getLogger(__name__)

__all__ = ["ServingApp", "make_server", "run_server"]

_MAX_BODY = 64 * 1024 * 1024  # refuse absurd payloads before np.asarray
_POLL_S = 0.005  # how often the writer retries a client that reads nothing
_GRACE_S = 2.0  # how long unsent bytes may hold a stream that must end


def _sse(obj: dict) -> bytes:
    return b"data: " + json.dumps(obj).encode() + b"\n\n"


def _chunk(data: bytes) -> bytes:
    """One HTTP/1.1 chunked-transfer frame (``b""`` terminates)."""
    return b"%x\r\n" % len(data) + data + b"\r\n"


class _GenerateStream:
    """Handle for one streamed /generate: the request future
    (error surface for terminations that never emit — expiry in the
    waiting queue, cancel, shutdown), the decoder that owns the slot
    (the disconnect path calls ``cancel`` on exactly this one, which
    matters under dp routing), and the prompt length for the final
    frame. The rest is the stream writer's, touched by its
    thread alone: the socket once attached, the bytes not yet sent, and
    what has gone out; the handler thread sleeps on ``first`` (the first
    tokens frame is out, or the stream ended without one) and ``ended``
    (the terminating chunk is out, or the stream was given up)."""

    __slots__ = ("rid", "future", "decoder", "prompt_len", "sock", "out",
                 "sent", "first_at", "n_out", "closed", "ok", "first_out",
                 "first", "ended")

    def __init__(self, rid, decoder, prompt_len):
        self.rid = rid
        self.future = None  # set once submit returns
        self.decoder = decoder
        self.prompt_len = prompt_len
        self.sock = None
        self.out = bytearray()
        self.sent = 0
        self.first_at = None  # `sent` once the first tokens frame is out
        self.n_out = 0
        self.closed = False  # the terminating chunk is queued
        self.ok = False  # the done frame is queued
        self.first_out = False
        self.first = threading.Event()
        self.ended = threading.Event()


_TOKENS, _END, _ATTACH, _DROP = range(4)


class _StreamWriter:
    """The one thread that writes every streamed /generate response of
    an app. The decode loop's emit appends ``(stream, tokens,
    done)`` to one deque and wakes the thread only if it sleeps, so the
    64 emits of a round cost one wake-up, or a few, and never block the
    engine lock they run under. Each pass drains the deque, encodes the
    frames and sends each stream's bytes in one non-blocking ``send``;
    what a client does not take waits in its stream's buffer and goes
    out when its socket is writable, so one slow client stalls no other.
    A failed send cancels the request once and ends the stream.

    Counters: ``stream_frames_total`` (SSE frames written),
    ``stream_writer_passes_total`` (passes that wrote one or more), and
    ``stream_writer_cpu_seconds_total`` (the thread's CPU over its
    passes: one clock pair a pass, never a frame)."""

    def __init__(self, metrics):
        self._items = collections.deque()
        self._wake = threading.Event()
        self._lock = threading.Lock()  # the thread's start and stop
        self._thread = None
        self._closing = False
        self._stopped = False
        # the writer thread's own
        self._open = set()  # attached streams not yet ended
        self._blocked = set()  # streams whose socket is in the selector
        self._sel = None
        self._frames = metrics.counter(
            "stream_frames_total",
            "SSE frames written to streamed /generate responses")
        self._passes = metrics.counter(
            "stream_writer_passes_total",
            "stream writer passes that wrote at least one frame")
        self._cpu = (None, metrics.counter(
            "stream_writer_cpu_seconds_total",
            "stream writer thread CPU seconds over its passes"))

    # ------------------------------------------------ from other threads
    def _put(self, stream, kind, arg=None) -> None:
        self._items.append((stream, kind, arg))
        if not self._wake.is_set():
            self._wake.set()

    def sink(self, stream):
        """The emit sink for ``decoder.submit`` (never blocks: it runs
        under the engine lock), and the thread started if it is not."""
        with self._lock:
            if self._thread is None and not self._stopped:
                self._thread = threading.Thread(
                    target=self._run, name="stream-writer", daemon=True)
                self._thread.start()
        return lambda new, done: self._put(stream, _TOKENS, (new, done))

    def watch(self, stream) -> None:
        """End the stream with an error frame if its future resolves
        before a done frame was queued: deadline expiry, cancel,
        shutdown (after a done frame the item is dropped)."""
        stream.future.add_done_callback(
            lambda _fut: self._put(stream, _END))

    def attach(self, stream, sock) -> None:
        """The headers are out: from here the writer owns the socket
        (non-blocking), and sends what it has held for the stream."""
        with self._lock:
            if not self._stopped:
                self._put(stream, _ATTACH, sock)
                return
        stream.first.set()
        stream.ended.set()

    def end(self, stream, error: str) -> None:
        """Queue ``{"error": error}`` and the terminating chunk, unless
        the stream's last frame is queued already."""
        self._put(stream, _END, error)

    def drop(self, stream) -> None:
        """Give the stream up, its unsent bytes with it."""
        with self._lock:
            if not self._stopped:
                self._put(stream, _DROP)
                return
        stream.ended.set()

    def close(self) -> None:
        """Finish the streams open now (their requests should be ended
        already: ``ServingApp.close`` closes the decoder first), for at
        most ``_GRACE_S``, then stop the thread."""
        with self._lock:
            self._closing = True
            t = self._thread
            if t is None:
                self._stopped = True
        self._wake.set()
        if t is not None:
            t.join(2 * _GRACE_S)

    # -------------------------------------------------- the writer thread
    def _run(self) -> None:
        self._sel = selectors.DefaultSelector()
        give_up = None
        try:
            while True:
                self._wake.wait(_POLL_S if self._blocked or give_up
                                else None)
                self._wake.clear()
                try:
                    with _obs_span("stream_write", self._cpu):
                        frames = self._pass()
                except Exception:  # the thread every stream depends on
                    logger.exception("stream writer pass failed")
                    frames = 0
                if frames:
                    self._passes.inc()
                    self._frames.inc(frames)
                if self._closing:
                    if give_up is None:
                        give_up = time.monotonic() + _GRACE_S
                    if not self._open or time.monotonic() > give_up:
                        break
        finally:
            with self._lock:
                self._stopped = True
            self._pass()  # what was put before the stop
            for s in list(self._open):
                self._release(s)
            self._sel.close()

    def _pass(self) -> int:
        frames = 0
        touched = {}  # streams in the order the pass met them
        while True:
            try:
                stream, kind, arg = self._items.popleft()
            except IndexError:
                break
            try:
                frames += self._take(stream, kind, arg)
            except Exception:  # one bad item must not end every stream
                logger.exception("stream writer failed (rid=%s)",
                                 stream.rid)
                self._fail(stream)
            touched[stream] = None
        for s in touched:
            if s.sock is not None and s.out:
                self._send(s)
        if self._blocked:
            for key, _ in self._sel.select(0):
                self._send(key.data)
        return frames

    def _take(self, s, kind, arg) -> int:
        """Queue one item's bytes in its stream; the frames it made."""
        if kind == _ATTACH:
            s.sock = arg
            self._open.add(s)
            return 0
        if kind == _DROP:
            self._release(s)
            return 0
        if s.closed:  # nothing follows the terminating chunk
            return 0
        if kind == _TOKENS:
            toks, done = arg
            s.out += _chunk(_sse({"tokens": toks}))
            if s.first_at is None:
                s.first_at = s.sent + len(s.out)
            s.n_out += len(toks)
            if not done:
                return 1
            s.out += _chunk(_sse({"done": True,
                                  "prompt_len": s.prompt_len,
                                  "tokens_out": s.n_out})) + _chunk(b"")
            s.closed = s.ok = True
            return 2
        if arg is None:  # the future resolved with no done frame queued
            try:
                s.future.result(0)
                arg = "stream ended without tokens"
            except Exception as e:
                arg = str(e)
        s.out += _chunk(_sse({"error": arg})) + _chunk(b"")
        s.closed = True
        return 1

    def _send(self, s) -> None:
        if s.first_at is not None and not s.first_out:
            rt = _reqtrace.get()
            if rt is not None and s.sent == 0:
                # the first byte is about to hit the wire: THIS is the
                # TTFT the client feels, and what --slo judges
                rt.note_first_byte(s.rid)
        try:
            n = s.sock.send(s.out)
        except BlockingIOError:
            n = 0
        except OSError:
            # the client went away mid-stream: free the slot and its KV
            # page reservation NOW instead of decoding into a dead pipe
            self._fail(s)
            return
        del s.out[:n]
        s.sent += n
        if (not s.first_out and s.first_at is not None
                and s.sent >= s.first_at):
            s.first_out = True
            s.first.set()
        if s.out:
            if s not in self._blocked:
                self._sel.register(s.sock, selectors.EVENT_WRITE, s)
                self._blocked.add(s)
        elif s.closed:
            self._release(s)
        elif s in self._blocked:
            self._sel.unregister(s.sock)
            self._blocked.discard(s)

    def _fail(self, s) -> None:
        if s.sock is not None or not s.closed:  # not released yet: once
            s.decoder.cancel(s.rid)
        s.ok = False
        self._release(s)

    def _release(self, s) -> None:
        """The stream is over for the writer: wake its handler."""
        if s in self._blocked:
            self._sel.unregister(s.sock)
            self._blocked.discard(s)
        s.sock = None
        s.out.clear()
        s.closed = True
        self._open.discard(s)
        s.first.set()
        s.ended.set()


class ServingApp:
    """The wiring between HTTP handlers and the serving stack: engine
    (+ optional batcher) for /predict, decoder for /generate, one
    metrics registry for everything. Endpoint handlers return
    ``(status, payload_dict)`` so they are unit-testable without
    sockets.

    ``default_deadline_ms`` bounds every request (a per-request
    ``"deadline_ms"`` field overrides it); ``shed_generate_frac`` is the
    overload tier: when the predict queue or the decode waiting queue
    passes that fraction of its capacity, ``/generate`` sheds with 429
    while ``/predict`` keeps admitting. ``watchdog`` supplies the
    readiness verdict for ``/readyz``.

    dp mode (ISSUE 16): pass ``replicas`` (a
    :class:`bigdl_tpu.serving.replicas.ReplicaSet`) INSTEAD of
    engine/batcher/decoder/watchdog — every request routes to the
    least-loaded live replica, ``/readyz`` aggregates per-replica
    health (200 while >= 1 lives), and shedding goes fleet-level (only
    when every live replica is saturated)."""

    def __init__(self, *, name: str, metrics, engine=None, batcher=None,
                 decoder=None, request_timeout_s: float = 120.0,
                 default_deadline_ms: Optional[float] = None,
                 shed_generate_frac: float = 0.75,
                 watchdog=None, replicas=None, version: str = "v0",
                 clock=time.monotonic):
        if replicas is not None and (engine is not None
                                     or batcher is not None
                                     or decoder is not None):
            raise ValueError("pass either replicas= or "
                             "engine/batcher/decoder, not both")
        self.name = name
        self.metrics = metrics
        self.engine = engine
        self.batcher = batcher
        self.decoder = decoder
        self.watchdog = watchdog
        self.replicas = replicas
        self.clock = clock
        # the weights generation served right now — bumped by the fleet
        # rolling swap (ISSUE 20) and echoed as x-model-version on every
        # response so a client can prove which weights answered it
        self.model_version = str(version)
        # extension point for process-role routes (the fleet worker's
        # /control/state heartbeat and /admin/reload) — keyed
        # ("GET"|"POST", path), handler returns (status, body_dict)
        self.extra_routes = {}
        self.request_timeout_s = float(request_timeout_s)
        self.default_deadline_ms = (float(default_deadline_ms)
                                    if default_deadline_ms else None)
        if not 0.0 < shed_generate_frac <= 1.0:
            raise ValueError(f"shed_generate_frac must be in (0, 1], "
                             f"got {shed_generate_frac}")
        self.shed_generate_frac = float(shed_generate_frac)
        self._m_requests = {
            ep: metrics.counter(f"requests_{ep}_total",
                                f"completed /{ep} requests")
            for ep in ("predict", "generate")}
        self._m_errors = metrics.counter(
            "request_errors_total", "requests answered 4xx/5xx")
        self._m_expired = metrics.counter(
            "requests_expired_total",
            "requests answered 504 (deadline expired before compute)")
        self._m_shed = metrics.counter(
            "requests_shed_total",
            "requests shed 429 by tiered overload degradation")
        self._m_worker_dead = metrics.counter(
            "requests_worker_dead_total",
            "requests answered 503 fast (dead/wedged worker)")
        self._m_injected = metrics.counter(
            "faults_injected_requests_total",
            "requests failed by an installed --faultPlan")
        # the handlers' CPU of streamed requests: admission, headers and
        # two wake-ups; the frames are the stream writer's, whose CPU its
        # own counter takes, and the two together are what streams cost
        self._c_stream = (None, metrics.counter(
            "stream_handler_cpu_seconds_total",
            "handler-thread CPU seconds of streamed /generate requests, "
            "admission to the terminating chunk"))
        self._writer = _StreamWriter(metrics)
        self._m_latency = {
            ep: metrics.histogram(f"latency_{ep}_ms",
                                  f"/{ep} request latency (receipt to "
                                  f"response ready)")
            for ep in ("predict", "generate")}

    # ------------------------------------------------------------ deadlines
    def _deadline_from(self, payload: dict) -> Optional[float]:
        """Absolute per-request deadline on the app clock, from the
        request's ``deadline_ms`` or the server default (None = no
        deadline)."""
        ms = payload.get("deadline_ms", self.default_deadline_ms)
        if ms is None:
            return None
        return self.clock() + float(ms) / 1000.0

    # ------------------------------------------------------------- overload
    def _shed_generate(self) -> bool:
        """Tiered degradation: past ``shed_generate_frac`` of either
        queue's capacity — or with the SLO burn rate saturated (ISSUE
        15: every recently finished request is missing its targets, so
        admitting more only makes the backlog later) — /generate sheds
        so /predict keeps breathing."""
        frac = self.shed_generate_frac
        if self.replicas is not None:
            if self.replicas.shed_generate(frac):
                return True
        if (self.batcher is not None
                and self.batcher.queue_depth
                >= frac * self.batcher.max_queue):
            return True
        if (self.decoder is not None
                and len(self.decoder._waiting)
                >= frac * self.decoder.max_waiting):
            return True
        rt = _reqtrace.get()
        if rt is not None and rt.slo is not None and rt.slo.should_shed():
            return True
        return False

    # ------------------------------------------------------------ endpoints
    def handle_healthz(self):
        """Liveness only — a degraded-but-serving process answers 200
        here (and 503 on /readyz) so orchestrators drain it instead of
        killing it."""
        return 200, {"status": "ok", "model": self.name}

    def handle_readyz(self):
        if self.replicas is not None:
            # fleet readiness: 200 while >= 1 replica can serve (dead
            # replicas are routed around); detail names every verdict
            ok, detail = self.replicas.ready_detail()
            detail["model"] = self.name
            if self._shed_generate():
                detail["shedding"] = "generate"
            detail["status"] = "ready" if ok else "unready"
            return (200 if ok else 503), detail
        detail = {"model": self.name}
        ok = True
        if self.watchdog is not None and not self.watchdog.ready():
            ok = False
            detail["failed_workers"] = self.watchdog.failures
        for comp_name, comp in (("batcher", self.batcher),
                                ("decoder", self.decoder)):
            if comp is not None and not comp.alive():
                ok = False
                detail.setdefault("dead", []).append(comp_name)
        if self._shed_generate():
            detail["shedding"] = "generate"
        detail["status"] = "ready" if ok else "unready"
        return (200 if ok else 503), detail

    def _route(self, endpoint: str, rid: Optional[str]):
        """dp routing (ISSUE 16): pick the least-loaded live replica
        (raises WorkerDied -> 503 when none live) and stamp the choice
        into the request's lifecycle record; single-replica mode returns
        the app's own components unchanged."""
        if self.replicas is None:
            return self.engine, self.batcher, self.decoder
        rep = (self.replicas.pick_predict() if endpoint == "predict"
               else self.replicas.pick_generate())
        rt = _reqtrace.get()
        if rt is not None:
            rt.note_replica(rid, rep.index)
        return rep.engine, rep.batcher, rep.decoder

    def handle_predict(self, payload: dict, rid: Optional[str] = None):
        engine, batcher, _ = self._route("predict", rid)
        if engine is None:
            return 400, {"error": "no /predict engine for this model"}
        inputs = payload.get("inputs")
        if inputs is None:
            return 400, {"error": "missing 'inputs'"}
        try:
            x = np.asarray(inputs)
            if x.dtype == object:
                raise ValueError("ragged inputs")
            if np.issubdtype(x.dtype, np.floating):
                x = x.astype(np.float32)
            elif np.issubdtype(x.dtype, np.integer):
                x = x.astype(np.int32)
            else:
                raise ValueError(f"unsupported dtype {x.dtype}")
        except ValueError as e:
            return 400, {"error": f"bad inputs: {e}"}
        if x.ndim < 2:
            return 400, {"error": "inputs must be a batch (rows on "
                                  "axis 0)"}
        deadline = self._deadline_from(payload)
        if batcher is not None:
            futs = [batcher.submit(row, deadline=deadline, rid=rid)
                    for row in x]
            scores = np.stack([f.result(self.request_timeout_s)
                               for f in futs])
        else:
            if deadline is not None and self.clock() >= deadline:
                raise DeadlineExceeded("deadline expired before compute")
            scores = engine.predict_scores(
                x, rids=([rid] * len(x) if rid is not None else None))
        preds = np.argmax(scores, axis=-1)
        out = {"predictions": preds.tolist()}
        if payload.get("return_scores"):
            out["scores"] = np.asarray(scores, np.float64).tolist()
        return 200, out

    @staticmethod
    def _parse_generate(payload: dict):
        """Validate the /generate payload; ``(parsed, None)`` or
        ``(None, error_string)`` — shared by the buffered and streamed
        paths so the two can never diverge on what they admit."""
        tokens = payload.get("tokens")
        if (not isinstance(tokens, (list, tuple)) or not tokens
                or not all(isinstance(t, int) for t in tokens)):
            return None, "'tokens' must be a non-empty list of ints"
        try:
            opts = {"max_new": payload.get("max_new_tokens", 16),
                    "temperature": payload.get("temperature", 0.0),
                    "stop": payload.get("stop_token"),
                    "top_k": int(payload.get("top_k", 0)),
                    "top_p": float(payload.get("top_p", 1.0)),
                    "seed": int(payload.get("seed", 0))}
        except (TypeError, ValueError):
            return None, "'top_k'/'seed' must be ints, 'top_p' a float"
        return (list(tokens), opts), None

    def handle_generate(self, payload: dict, rid: Optional[str] = None):
        _, _, decoder = self._route("generate", rid)
        if decoder is None:
            return 400, {"error": "no /generate decoder for this model "
                                  "(serve a transformer_lm* model)"}
        parsed, err = self._parse_generate(payload)
        if parsed is None:
            return 400, {"error": err}
        tokens, o = parsed
        try:
            fut = decoder.submit(tokens, o["max_new"], o["temperature"],
                                 o["stop"],
                                 deadline=self._deadline_from(payload),
                                 top_k=o["top_k"], top_p=o["top_p"],
                                 seed=o["seed"], rid=rid)
        except ValueError as e:
            return 400, {"error": str(e)}
        out_tokens = fut.result(self.request_timeout_s)
        return 200, {"tokens": out_tokens,
                     "prompt_len": len(tokens)}

    # ------------------------------------------------------------- streaming
    def start_generate_stream(self, payload: dict,
                              rid: Optional[str] = None):
        """Admission for a streamed /generate (ISSUE 18): same shed /
        validation / error ladder as :meth:`dispatch_post`, but instead
        of blocking on the future it submits with the stream writer's
        emit sink and returns ``(200, _GenerateStream)`` for the HTTP
        handler to attach. Every pre-stream failure returns a plain
        ``(status, body)`` — errors before the first byte stay ordinary
        JSON responses."""
        rt = _reqtrace.get()
        if rt is not None:
            toks = payload.get("tokens")
            prompt_n = (len(toks) if isinstance(toks, (list, tuple))
                        else None)
            try:
                max_new = int(payload.get("max_new_tokens", 16))
            except (TypeError, ValueError):
                max_new = None
            rid = rt.admit("generate", rid, prompt_tokens=prompt_n,
                           max_new=max_new)
        if self._shed_generate():
            self._m_shed.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "shed", status=429)
            return 429, {"error": "overloaded: shedding /generate "
                                  "(retry, or use /predict capacity)"}
        parsed, err = self._parse_generate(payload)
        if parsed is None:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "bad_request", status=400, error=err)
            return 400, {"error": err}
        tokens, o = parsed
        try:
            _fault_hook("request")  # no-op unless --faultPlan installed
            _, _, decoder = self._route("generate", rid)
            if decoder is None:
                err = ("no /generate decoder for this model "
                       "(serve a transformer_lm* model)")
                self._m_errors.inc()
                if rt is not None:
                    rt.finish(rid, "bad_request", status=400, error=err)
                return 400, {"error": err}
            # emit runs under the engine lock: only hand the round's
            # tokens to the stream writer, never block
            stream = _GenerateStream(rid, decoder, len(tokens))
            stream.future = decoder.submit(
                tokens, o["max_new"], o["temperature"], o["stop"],
                deadline=self._deadline_from(payload),
                top_k=o["top_k"], top_p=o["top_p"], seed=o["seed"],
                rid=rid, emit=self._writer.sink(stream))
            self._writer.watch(stream)
        except ValueError as e:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "bad_request", status=400, error=str(e))
            return 400, {"error": str(e)}
        except AdmissionError as e:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "rejected", status=429, error=str(e))
            return 429, {"error": str(e)}
        except DeadlineExceeded as e:
            self._m_expired.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "expired", status=504, error=str(e))
            return 504, {"error": f"deadline exceeded: {e}"}
        except WorkerDied as e:
            self._m_worker_dead.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "worker_dead", status=503, error=str(e))
            return 503, {"error": str(e)}
        except TransientFault as e:
            self._m_injected.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "error", status=503,
                          error=f"injected fault: {e}")
            return 503, {"error": f"injected fault: {e}"}
        except Exception as e:
            logger.exception("/generate stream admission failed")
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "error", status=500,
                          error=f"{type(e).__name__}: {e}")
            return 500, {"error": f"{type(e).__name__}: {e}"}
        return 200, stream

    def finish_generate_stream(self, rid: Optional[str], ok: bool,
                               t0: float) -> None:
        """Account a drained stream the way :meth:`dispatch_post`
        accounts a buffered response: request/latency metrics and the
        lifecycle status annotation on success (the engine already
        terminalized the record — this only fills in HTTP 200), error
        counter otherwise (the terminal state was stamped where the
        failure happened)."""
        if ok:
            self._m_requests["generate"].inc()
            self._m_latency["generate"].observe(
                (time.perf_counter() - t0) * 1000.0)
            rt = _reqtrace.get()
            if rt is not None:
                rt.finish(rid, "finished", status=200)
        else:
            self._m_errors.inc()

    def handle_metrics(self) -> str:
        return self.metrics.render()

    def handle_debug_requests(self):
        """Live flight-recorder view (ISSUE 15): in-flight request
        states + the recent completed ring. 404 while ``--reqTrace`` is
        off — the recorder does not exist, which is itself the
        answer."""
        rt = _reqtrace.get()
        if rt is None:
            return 404, {"enabled": False,
                         "error": "request tracing off (start with "
                                  "--reqTrace on)"}
        return 200, rt.snapshot()

    def handle_debug_slots(self):
        """Decoder slot table + KV page-pool occupancy + batcher queue
        depth — works regardless of ``--reqTrace`` (it reads engine
        state, not lifecycle records). dp mode returns one snapshot per
        replica."""
        if self.replicas is not None:
            return 200, self.replicas.debug_snapshot()
        if self.decoder is not None:
            out = self.decoder.debug_snapshot()
        else:
            out = {"slots": [], "slots_total": 0, "slots_active": 0,
                   "waiting": 0, "kv": {"paged": False}}
        if self.batcher is not None:
            out["batcher"] = {
                "queue_depth": self.batcher.queue_depth,
                "max_queue": self.batcher.max_queue,
                "worker_up": self.batcher.alive()}
        return 200, out

    # ------------------------------------------------------------- dispatch
    def dispatch_post(self, path: str, payload: dict,
                      rid: Optional[str] = None):
        ep = path.strip("/")
        handler = {"predict": self.handle_predict,
                   "generate": self.handle_generate}.get(ep)
        if handler is None:
            return 404, {"error": f"unknown endpoint {path}"}
        # lifecycle record opens at admission (ISSUE 15): even a shed or
        # rejected request leaves an autopsy trail
        rt = _reqtrace.get()
        if rt is not None:
            prompt_n = max_new = None
            if ep == "generate":
                toks = payload.get("tokens")
                if isinstance(toks, (list, tuple)):
                    prompt_n = len(toks)
                try:
                    max_new = int(payload.get("max_new_tokens", 16))
                except (TypeError, ValueError):
                    max_new = None
            rid = rt.admit(ep, rid, prompt_tokens=prompt_n,
                           max_new=max_new)
        if ep == "generate" and self._shed_generate():
            # tiered degradation: /generate sheds first so /predict
            # keeps its admission headroom under overload
            self._m_shed.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "shed", status=429)
            return 429, {"error": "overloaded: shedding /generate "
                                  "(retry, or use /predict capacity)"}
        t0 = time.perf_counter()
        try:
            _fault_hook("request")  # no-op unless --faultPlan installed
            with _obs_span("request", endpoint=ep):
                status, body = handler(payload, rid=rid)
        except AdmissionError as e:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "rejected", status=429, error=str(e))
            return 429, {"error": str(e)}
        except DeadlineExceeded as e:
            self._m_expired.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "expired", status=504, error=str(e))
            return 504, {"error": f"deadline exceeded: {e}"}
        except WorkerDied as e:
            self._m_worker_dead.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "worker_dead", status=503, error=str(e))
            return 503, {"error": str(e)}
        except TransientFault as e:
            self._m_injected.inc()
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "error", status=503,
                          error=f"injected fault: {e}")
            return 503, {"error": f"injected fault: {e}"}
        except TimeoutError as e:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "error", status=503, error=str(e))
            return 503, {"error": str(e)}
        except Exception as e:
            logger.exception("/%s failed", ep)
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid, "error", status=500,
                          error=f"{type(e).__name__}: {e}")
            return 500, {"error": f"{type(e).__name__}: {e}"}
        if status == 200:
            self._m_requests[ep].inc()
            self._m_latency[ep].observe((time.perf_counter() - t0) * 1000.0)
            if rt is not None:
                # decode-path records already finished inside the
                # engine (honest t_finish); this is a no-op there and
                # terminalizes the predict path
                rt.finish(rid, "finished", status=200)
        else:
            self._m_errors.inc()
            if rt is not None:
                rt.finish(rid,
                          "bad_request" if status == 400 else "error",
                          status=status,
                          error=str(body.get("error", "")) or None)
        return status, body

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.batcher is not None:
            self.batcher.close()
        if self.decoder is not None:
            self.decoder.close()
        if self.replicas is not None:
            self.replicas.close()
        self._writer.close()  # the streams' error frames, then the thread
        rt = _reqtrace.get()
        if rt is not None:
            rt.close()  # flush the access log


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    @property
    def app(self) -> ServingApp:
        return self.server.app  # type: ignore[attr-defined]

    def _rid(self) -> str:
        """The request id echoed on EVERY response (ISSUE 15): a valid
        client-supplied ``x-request-id`` wins (so the caller can join
        server records to its own logs), else one is minted — with or
        without tracing enabled."""
        return (_reqtrace.sanitize_rid(self.headers.get("x-request-id"))
                or _reqtrace.mint_rid())

    def _send_json(self, status: int, body: dict,
                   rid: Optional[str] = None) -> None:
        data = json.dumps(body).encode()
        self.send_response(status)
        if status == 429:
            self.send_header("Retry-After", "1")
        if rid is not None:
            self.send_header("x-request-id", rid)
        version = getattr(self.app, "model_version", None)
        if version:
            self.send_header("x-model-version", str(version))
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):  # noqa: N802 (stdlib naming)
        rid = self._rid()
        if self.path == "/healthz":
            self._send_json(*self.app.handle_healthz(), rid=rid)
        elif self.path == "/readyz":
            self._send_json(*self.app.handle_readyz(), rid=rid)
        elif self.path == "/debug/requests":
            self._send_json(*self.app.handle_debug_requests(), rid=rid)
        elif self.path == "/debug/slots":
            self._send_json(*self.app.handle_debug_slots(), rid=rid)
        elif self.path == "/metrics":
            data = self.app.handle_metrics().encode()
            self.send_response(200)
            self.send_header("x-request-id", rid)
            version = getattr(self.app, "model_version", None)
            if version:
                self.send_header("x-model-version", str(version))
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        elif ("GET", self.path) in getattr(self.app, "extra_routes", {}):
            handler = self.app.extra_routes[("GET", self.path)]
            self._send_json(*handler(None), rid=rid)
        else:
            self._send_json(404, {"error": f"unknown path {self.path}"},
                            rid=rid)

    def do_POST(self):  # noqa: N802
        rid = self._rid()
        try:
            length = int(self.headers.get("Content-Length", 0))
        except ValueError:
            length = 0
        if length <= 0 or length > _MAX_BODY:
            self._send_json(400, {"error": "missing or oversized body"},
                            rid=rid)
            return
        try:
            payload = json.loads(self.rfile.read(length))
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, json.JSONDecodeError) as e:
            self._send_json(400, {"error": f"bad JSON: {e}"}, rid=rid)
            return
        if ("POST", self.path) in getattr(self.app, "extra_routes", {}):
            handler = self.app.extra_routes[("POST", self.path)]
            self._send_json(*handler(payload), rid=rid)
            return
        if self.path.strip("/") == "generate" and payload.get("stream"):
            with _obs_span("generate_request", self.app._c_stream,
                           rid=rid):
                self._stream_generate(payload, rid)
            return
        status, body = self.app.dispatch_post(self.path, payload,
                                              rid=rid)
        self._send_json(status, body, rid=rid)

    # ------------------------------------------------------------- streaming
    def _stream_generate(self, payload: dict, rid: str) -> None:
        """Streamed /generate (ISSUE 18): chunked-transfer SSE frames,
        one per emitting decode round (only ACCEPTED tokens under
        ``--speculate``, so concatenating the frames is bit-identical to
        the buffered response), a final ``{"done": true}`` frame, and
        client-disconnect detection — a failed write cancels the slot
        mid-decode, releasing its paged-KV pages back to the
        allocator. This thread admits and sends the headers; the app's
        stream writer sends the frames, and this thread
        returns once the terminating chunk is out."""
        app = self.app
        t0 = time.perf_counter()
        with _obs_span("generate_admit", rid=rid):
            status, obj = app.start_generate_stream(payload, rid=rid)
        if status != 200:
            self._send_json(status, obj, rid=rid)
            return
        stream: _GenerateStream = obj
        writer = app._writer
        deadline = time.monotonic() + app.request_timeout_s
        # the span that is open: the wait for the first token, from
        # submit's return; then one span from the first frame to the last
        phase = contextlib.ExitStack()
        phase.enter_context(_obs_span("generate_first_token_wait", rid=rid))
        try:
            self.send_response(200)
            self.send_header("x-request-id", rid)
            version = getattr(app, "model_version", None)
            if version:
                self.send_header("x-model-version", str(version))
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.send_header("Connection", "close")
            self.end_headers()
            self.close_connection = True
            self.connection.setblocking(False)
            writer.attach(stream, self.connection)
            if stream.first.wait(app.request_timeout_s) and stream.first_out:
                phase.close()
                phase.enter_context(_obs_span("generate_stream", rid=rid))
            if not stream.ended.wait(max(0.0, deadline - time.monotonic())):
                writer.end(stream, "stream timeout")
                stream.decoder.cancel(rid, reason="server stream timeout")
                if not stream.ended.wait(_GRACE_S):  # a client that reads
                    writer.drop(stream)  # nothing keeps no thread
                    stream.ended.wait(_GRACE_S)
        except OSError:
            # the client went away before the headers were out
            stream.decoder.cancel(rid)
        finally:
            phase.close()
            app.finish_generate_stream(rid, stream.ok, t0)

    def log_message(self, fmt, *args):  # route access logs to logging
        logger.debug("%s - %s", self.address_string(), fmt % args)


def make_server(app: ServingApp, host: str = "127.0.0.1",
                port: int = 8000) -> ThreadingHTTPServer:
    """Bind (port 0 = ephemeral, for tests) and attach the app; the
    caller runs ``serve_forever`` (or a thread does)."""
    srv = ThreadingHTTPServer((host, port), _Handler)
    srv.daemon_threads = True
    srv.app = app  # type: ignore[attr-defined]
    return srv


def run_server(app: ServingApp, host: str = "127.0.0.1",
               port: int = 8000,
               ready_event: Optional[threading.Event] = None) -> int:
    """Foreground serve loop with clean SIGINT/SIGTERM shutdown (the CI
    smoke asserts exit code 0 after SIGTERM). Returns 0."""
    import signal

    srv = make_server(app, host, port)
    actual = srv.server_address[1]
    logger.info("serving %s on http://%s:%d (/predict /generate /healthz "
                "/readyz /metrics)", app.name, host, actual)
    print(f"serving {app.name} on http://{host}:{actual}", flush=True)

    def _stop(signum, frame):
        # shutdown() must come from another thread than serve_forever's
        threading.Thread(target=srv.shutdown, daemon=True).start()

    prev = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev[sig] = signal.signal(sig, _stop)
        except ValueError:  # non-main thread (tests drive make_server)
            pass
    if ready_event is not None:
        ready_event.set()
    try:
        srv.serve_forever(poll_interval=0.2)
    finally:
        for sig, h in prev.items():
            signal.signal(sig, h)
        srv.server_close()
        app.close()
        print("serving shutdown clean", flush=True)
    return 0
