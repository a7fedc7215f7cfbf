"""Serving cells (traffic kinds ``open_loop`` and ``closed_loop``): the
program's ``DecodeEngine`` + ``ServingApp`` + HTTP handler, wired as
``cli/serve.py build_app`` wires them for the default ``serve`` path
(dense cache, no paging, speculation or quantization; ``serve`` has no
``--numKvHeads`` and no 3B preset, so the objects are built here), bound
to loopback on port 0 and driven over real HTTP ``/generate`` with
``"stream": true`` from threads of this one process."""

import http.client
import json
import threading
import time

import numpy as np

from . import reference, trace, traffic as tg


# ------------------------------------------------------------------ client
def stream_generate(port, tokens, max_new, rec, timeout=300.0):
    """POST one streamed /generate and stamp the arrival of every SSE
    frame into ``rec`` (the reader of scripts/serving_bench.py, by line)."""
    rec["sent"] = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate", json.dumps(
            {"tokens": tokens, "max_new_tokens": max_new,
             "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read().decode(errors="replace")[:200]
            return
        while True:
            line = resp.readline()
            if not line:
                rec["error"] = "stream ended without a done frame"
                return
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter()
            frame = json.loads(line[6:])
            if "tokens" in frame:
                rec["arrivals"].append((now, len(frame["tokens"])))
                rec["out"].extend(frame["tokens"])
            elif frame.get("done"):
                rec["done"] = now
                return
            elif "error" in frame:
                rec["error"] = str(frame["error"])[:200]
                return
    except OSError as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


class Load:
    """The one general generator: requests in stratified blocks of K
    length pairs (traffic.py), issued open loop at the file's
    ``rate_rps`` or closed loop by its ``clients``."""

    def __init__(self, port, mix, seed, vocab):
        self.port, self.mix, self.seed, self.vocab = port, mix, seed, vocab
        self.pairs = tg.blocks(tg.length_pairs(mix), seed, "lengths")
        self.records, self.threads = [], []
        self.lock = threading.Lock()
        self.stop = threading.Event()

    def _next(self, due=None):
        with self.lock:
            p, o = next(self.pairs)
            rec = {"index": len(self.records), "prompt_tokens": p,
                   "max_new": o, "due": due, "arrivals": [], "out": []}
            self.records.append(rec)
        return rec

    def _one(self, rec):
        toks = tg.token_ids(self.seed, rec["index"], rec["prompt_tokens"],
                            self.vocab)
        with trace.annotate("request_to_first_token"):
            stream_generate(self.port, toks, rec["max_new"], rec)

    def _spawn(self, fn, *a):
        t = threading.Thread(target=fn, args=a, daemon=True)
        t.start()
        self.threads.append(t)

    def start(self, t_start, until_s):
        if self.mix["kind"] == "open_loop":
            due = tg.arrival_times(self.mix["rate_rps"], self.seed, until_s)
            self._spawn(self._open, t_start, due)
        else:
            n = self.mix["clients"]
            for i in range(n):  # rule 3: starts staggered across the ramp
                self._spawn(self._client,
                            t_start + self.mix["ramp_s"] * i / n)

    def _open(self, t_start, due):
        for d in due:
            delay = t_start + d - time.perf_counter()
            if delay > 0 and self.stop.wait(delay):
                return
            self._spawn(self._one, self._next(due=t_start + d))

    def _client(self, t_first):
        delay = t_first - time.perf_counter()
        if delay > 0 and self.stop.wait(delay):
            return
        while not self.stop.is_set():
            rec = self._next()
            self._one(rec)
            if "error" in rec:  # do not spin on a dead server
                self.stop.wait(0.5)

    def finish(self, timeout=5.0):
        self.stop.set()
        end = time.perf_counter() + timeout
        for t in list(self.threads):
            t.join(max(0.0, end - time.perf_counter()))


# ------------------------------------------------------------------ server
def build_server(ctx, model, params):
    """DecodeEngine, Watchdog, ServingApp and HTTP server, with the
    arguments ``build_app`` passes for ``serve --bf16 --slots N``."""
    from bigdl_tpu.obs.metrics import set_registry
    from bigdl_tpu.serving import (DecodeEngine, MetricsRegistry,
                                   ServingApp, Watchdog)
    from bigdl_tpu.serving.server import make_server

    sv = ctx["config"]["serve"]
    metrics = MetricsRegistry()
    set_registry(metrics)
    decoder = DecodeEngine(model, params, slots=sv["slots"],
                           cache_dtype=model.compute_dtype,
                           max_waiting=sv["max_waiting"], metrics=metrics)
    watchdog = Watchdog(stall_timeout_s=30.0, metrics=metrics)
    watchdog.watch("decoder", decoder)
    app = ServingApp(name=ctx["config"]["name"], metrics=metrics,
                     decoder=decoder, watchdog=watchdog)
    return decoder, watchdog, app, make_server(app, "127.0.0.1", 0)


def check_and_warm(ctx, decoder, params):
    """Outside the window, before the decode thread exists: (1) prefill
    then decode through the engine's cache against the reference's full
    forward on one seeded sequence, in logits; (2) one request through
    every prefill bucket this mix's sixteen prompt lengths fall into, so
    that nothing compiles inside the window."""
    margs, chk = ctx["config"]["model"], ctx["traffic"]["check"]
    parts, seed = ctx["parts"], ctx["seed"]
    t = time.perf_counter()
    n = chk["decode_steps"]
    toks = tg.token_ids(seed, "check", chk["prompt_tokens"], margs["vocab"])
    fut = decoder.submit(toks, n + 1)
    slot = next(i for i, r in enumerate(decoder._reqs) if r is not None)
    got = [np.asarray(decoder._logits)[slot]]
    for _ in range(n):
        decoder.step()
        got.append(np.asarray(decoder._logits)[slot])
    decoder.step()
    out = fut.result(0)
    want = np.asarray(reference.logits(params, margs, toks + out[:n]))
    want = want[len(toks) - 1:]
    scale = float(np.abs(want).max())
    err = max(float(np.abs(g - w).max()) for g, w in zip(got, want)) / scale
    parts["reference_and_first_programs_s"] = time.perf_counter() - t

    t = time.perf_counter()
    buckets = {}  # bucket -> the mix's longest prompt that falls into it
    for p, _ in tg.length_pairs(ctx["traffic"]):
        b = decoder.prompt_bucket_for(p)
        buckets[b] = max(p, buckets.get(b, 0))
    for b, p in sorted(buckets.items()):
        if b != decoder.prompt_bucket_for(len(toks)):
            t_b = time.perf_counter()
            decoder.generate(tg.token_ids(seed, f"warm{b}", p,
                                          margs["vocab"]), 2)
            parts[f"prefill_bucket_{b}_s"] = time.perf_counter() - t_b
    parts["warm_buckets_s"] = time.perf_counter() - t
    return {"logits_rel_err": err, "logits_scale": scale,
            "prefill_buckets": sorted(buckets),
            "greedy_tokens_in_vocab": all(0 <= v < margs["vocab"]
                                          for v in out)}


def run(ctx):
    import jax
    import jax.numpy as jnp

    from .model import build_model, seeded_params

    cfg, mix, parts = ctx["config"], ctx["traffic"], ctx["parts"]
    margs = cfg["model"]
    on_tpu = ctx["device"]["platform"] == "tpu"

    t = time.perf_counter()
    dtype = jnp.dtype(cfg["serve"]["dtype"])
    model = build_model(cfg, attn_impl="flash" if on_tpu else None,
                        compute_dtype=dtype)
    params = seeded_params(model, ctx["seed"], dtype)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    decoder, watchdog, app, srv = build_server(ctx, model, params)
    parts["engine_and_cache_s"] = time.perf_counter() - t
    checks = check_and_warm(ctx, decoder, params)

    decoder.start()
    watchdog.start()
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, kwargs={"poll_interval": 0.2},
                     daemon=True).start()
    warm = {"arrivals": [], "out": []}
    stream_generate(port, tg.token_ids(  # a bucket that is warm already
        ctx["seed"], "http", mix["check"]["prompt_tokens"], margs["vocab"]),
        2, warm)
    if len(warm["out"]) != 2:
        raise RuntimeError(f"warm-up request over HTTP failed: {warm}")

    seconds, slice_s = ctx["seconds"], 0.0
    if ctx["trace"]:  # the traced slice follows a shortened scored window
        slice_s = min(mix["trace_slice_s"], seconds / 2)
        seconds -= slice_s
    load = Load(port, mix, ctx["seed"], margs["vocab"])
    t_start = time.perf_counter()
    t_open = t_start + mix["ramp_s"]
    t_close = t_open + seconds
    load.start(t_start, mix["ramp_s"] + seconds + slice_s)
    time.sleep(max(0.0, t_open - time.perf_counter()))
    parts["ramp_s"] = mix["ramp_s"]
    ctx["window_open"](t_open)
    time.sleep(max(0.0, t_close - time.perf_counter()))
    planes = None
    if ctx["trace"]:
        trace.start(ctx["trace_dir"])
        time.sleep(slice_s - 1.0)
        planes = trace.stop_and_load(ctx["trace_dir"])
    else:
        # first tokens of requests due just before the close
        time.sleep(mix.get("drain_s", 0.0))
    recs = load.records
    if mix["kind"] == "open_loop":
        scored = [r for r in recs if t_open <= r["due"] < t_close]
    else:
        scored = [r for r in recs if t_open <= r.get("sent", 0) < t_close]
    # wrong: refused, broken or truncated, or a token outside the
    # vocabulary. unanswered (open loop): no first token by the end of the
    # drain; it counts in `failed`, and its wait so far in the TTFT tail
    t_eval = time.perf_counter()
    wrong = [r for r in scored
             if "error" in r or r.get("status", 200) != 200
             or ("done" in r and len(r["out"]) != r["max_new"])
             or not all(0 <= v < margs["vocab"] for v in r["out"])]
    unanswered = [r for r in scored if r["due"] is not None
                  and not r["arrivals"] and r not in wrong]
    bad = wrong + unanswered
    prompt_tok, out_tok = tg.credited_tokens(recs, t_open, t_close)
    fifths = tg.credited_by_fifth(recs, t_open, t_close)
    gaps = tg.token_gaps_ms(recs, t_open, t_close)
    ttft = [(r["arrivals"][0][0] - (r["due"] or r["sent"])) * 1e3
            for r in scored if r["arrivals"]]
    ttft += [(t_eval - r["due"]) * 1e3 for r in unanswered]
    late = [(r["sent"] - r["due"]) * 1e3 for r in scored
            if r["due"] is not None and "sent" in r]
    ctx["info"]("window", requests_scored=len(scored), failed=len(bad),
                unanswered=len(unanswered), token_gaps=len(gaps),
                credited_prompt=prompt_tok, credited_output=out_tok,
                credited_by_fifth=fifths,
                first_errors=[r.get("error") for r in bad[:3]])
    checks["responses_exact"] = not wrong
    load.stop.set()
    app.close()  # ends the streams still open; then the clients return
    load.finish()
    srv.shutdown()
    srv.server_close()
    e2e = {"serve_tok_s": (prompt_tok + out_tok) / seconds,
           "itl_p50_ms": tg.percentile(gaps, 50),
           "ttft_p50_ms": tg.percentile(ttft, 50),
           "ttft_p90_ms": tg.percentile(ttft, 90)}
    return {
        "correct": bool(checks["logits_rel_err"] < mix["check"]["rel_tol"]
                        and checks["greedy_tokens_in_vocab"] and not wrong),
        "attempted": len(scored), "failed": len(bad), "e2e": e2e,
        "checks": checks,
        "run": {"kind": "serve", "planes": planes, "token_gaps_ms": gaps,
                "ttft_ms": ttft, "gen_late_ms": late,
                "credited_by_fifth": fifths},
    }
