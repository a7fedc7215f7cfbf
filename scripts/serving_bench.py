#!/usr/bin/env python
"""Closed-loop load generator for the `bigdl-tpu serve` endpoint
(ISSUE 5 satellite) — the serving analog of the perf harness: drive
/predict or /generate at a fixed concurrency, report client-side latency
quantiles (p50/p95/p99) and throughput, and stamp the SERVER's config
provenance (scraped from /metrics) into the emitted JSON line so every
result is attributable to an exact program — the perf-JSON contract from
PRs 2-4 extended to serving.

    # spawn a server on an ephemeral port, bench, shut down
    python scripts/serving_bench.py --model lenet5 --randomInit \
        --requests 64 --concurrency 4 --platform cpu

    # bench an already-running server
    python scripts/serving_bench.py --url http://127.0.0.1:8000 \
        --model resnet50 --endpoint predict --batch 4

    # CI smoke: tiny config, asserts endpoints + metrics + clean shutdown
    python scripts/serving_bench.py --smoke --model transformer_lm \
        --platform cpu

    # CI slo-smoke: ISSUE 15 per-request observability assertions
    # (SLO goodput/burn/shed, access log, /debug/*, x-request-id)
    python scripts/serving_bench.py --sloSmoke --model transformer_lm \
        --platform cpu

    # CI serving-tp-smoke: ISSUE 16 multi-chip assertions (tp:2
    # bit-identity vs single chip, dp:2 replica-labelled metrics)
    python scripts/serving_bench.py --tpSmoke --model transformer_lm \
        --platform cpu

    # dp QPS scaling sweep (the ISSUE 16 perf headline; on chips add
    # --assertScaling 0.8)
    python scripts/serving_bench.py --dpSweep 1,2,4 \
        --model transformer_lm --endpoint generate

    # CI fleet-smoke: ISSUE 20 multi-process fleet assertions (router
    # proxy, kill/restart/rejoin, zero-5xx rolling weight swap)
    python scripts/serving_bench.py --fleetSmoke --model transformer_lm \
        --platform cpu
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# input geometry per perf-zoo family (serving payload synthesis); LMs
# take their length from --seq
_SHAPES = {"lenet5": (28, 28, 1), "resnet20_cifar": (32, 32, 3)}
_DEFAULT_SHAPE = (224, 224, 3)

# tiny-LM dims for --smoke / --randomInit LM runs: CPU-fast, same code
# path as the 32k-vocab production config
_SMOKE_LM = ["--vocabSize", "64", "--dModel", "32", "--numLayers", "2",
             "--numHeads", "2", "--seq", "64", "--slots", "2",
             "--buckets", "1,2,4", "--maxWaitMs", "2"]


def _post(url, body, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _post_status(url, body, timeout=120.0):
    """Like _post but 4xx/5xx return (status, body) instead of raising
    — the chaos smoke asserts exact error codes."""
    try:
        return _post(url, body, timeout)
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except (ValueError, json.JSONDecodeError):
            return e.code, {}


def _post_h(url, body, headers=None, timeout=120.0):
    """POST returning (status, json_body, lowercased response headers)
    — the ISSUE 15 legs assert on the ``x-request-id`` echo, and 4xx/5xx
    return instead of raising (the shed leg asserts exact 429s)."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return (r.status, json.loads(r.read()),
                    {k.lower(): v for k, v in r.headers.items()})
    except urllib.error.HTTPError as e:
        try:
            out = json.loads(e.read())
        except (ValueError, json.JSONDecodeError):
            out = {}
        return e.code, out, {k.lower(): v for k, v in e.headers.items()}


def _get_status(url, timeout=30.0):
    try:
        return _get(url, timeout)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _get(url, timeout=30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def spawn_server(args, extra):
    """Launch `bigdl-tpu serve` as a child on an ephemeral port; parse
    the bound port from its stdout. Returns (proc, base_url, log_lines).
    """
    cmd = [sys.executable, "-m", "bigdl_tpu.cli.main", "serve",
           args.model, "--port", "0"]
    if args.ckpt:
        cmd += ["--model", args.ckpt]
    else:
        cmd += ["--randomInit"]
    if args.platform:
        cmd += ["--platform", args.platform]
    if args.model.startswith("transformer_lm") and (args.smoke
                                                    or not args.ckpt):
        cmd += _SMOKE_LM
    cmd += extra
    env = None
    if "--strategy" in cmd:
        # multi-chip strategies need devices to place replicas/shards on;
        # on the CPU host platform that means virtual devices (the same
        # trick tests/conftest.py uses). No-op on real accelerators.
        env = dict(os.environ)
        if "xla_force_host_platform_device_count" not in \
                env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    lines, port = [], None
    port_re = re.compile(r"serving .+ on http://[^:]+:(\d+)")
    ready = threading.Event()

    def _reader():
        nonlocal port
        for line in proc.stdout:
            lines.append(line.rstrip())
            m = port_re.search(line)
            if m:
                port = int(m.group(1))
                ready.set()
        ready.set()  # EOF: unblock the waiter even on startup failure

    threading.Thread(target=_reader, daemon=True).start()
    if not ready.wait(timeout=300) or port is None:
        proc.kill()
        raise SystemExit("server never reported its port; log tail:\n"
                         + "\n".join(lines[-20:]))
    url = f"http://127.0.0.1:{port}"
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            if _get(url + "/healthz", timeout=5)[0] == 200:
                return proc, url, lines
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.2)
    proc.kill()
    raise SystemExit("server bound but /healthz never answered")


def make_payload(args):
    import numpy as np
    rng = np.random.RandomState(0)
    if args.endpoint == "generate":
        seq = args.promptLen
        return {"tokens": rng.randint(1, 50, seq).tolist(),
                "max_new_tokens": args.maxNewTokens}
    if args.model.startswith("transformer_lm"):
        seq = 64 if (args.smoke or not args.ckpt) else (args.seq or 512)
        x = rng.randint(0, 50, (args.batch, seq)).tolist()
    else:
        shape = _SHAPES.get(args.model, _DEFAULT_SHAPE)
        x = rng.randn(args.batch, *shape).astype("float32").tolist()
    return {"inputs": x}


def closed_loop(url, args):
    """N workers, each fire-wait-fire until the shared budget drains.
    With ``--stream`` (generate only) each request rides the chunked
    SSE path instead, recording client-side first-byte latency — the
    streamed half of the r22 TTFT/TPOT A/B."""
    payload = make_payload(args)
    path = f"{url}/{args.endpoint}"
    stream = bool(getattr(args, "stream", False)) \
        and args.endpoint == "generate"
    lat, ttfb, errors, lock = [], [], [0], threading.Lock()
    budget = [args.requests]
    new_tokens = [0]

    def worker():
        while True:
            with lock:
                if budget[0] <= 0:
                    return
                budget[0] -= 1
            t0 = time.perf_counter()
            try:
                if stream:
                    st, frames, t_first, t_done, _ = _stream_generate(
                        url, payload)
                    assert st == 200, frames
                    toks = sum(len(f["tokens"]) for f in frames
                               if "tokens" in f)
                    with lock:
                        lat.append(t_done * 1000.0)
                        ttfb.append(t_first * 1000.0)
                        new_tokens[0] += toks
                    continue
                _, out = _post(path, payload)
                dt = (time.perf_counter() - t0) * 1000.0
                with lock:
                    lat.append(dt)
                    if args.endpoint == "generate":
                        new_tokens[0] += len(out.get("tokens", []))
            except Exception:
                with lock:
                    errors[0] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker)
               for _ in range(args.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    out = {
        "bench": "serving",
        "model": args.model,
        "endpoint": args.endpoint,
        "requests": args.requests,
        "concurrency": args.concurrency,
        "batch": args.batch if args.endpoint == "predict" else None,
        "wall_s": round(wall, 4),
        "rps": round(len(lat) / wall, 2) if wall else None,
        "errors": errors[0],
        "latency_ms": {
            "p50": round(_percentile(lat, 0.50), 3) if lat else None,
            "p95": round(_percentile(lat, 0.95), 3) if lat else None,
            "p99": round(_percentile(lat, 0.99), 3) if lat else None,
            "mean": round(sum(lat) / len(lat), 3) if lat else None,
            "max": round(lat[-1], 3) if lat else None,
        },
    }
    if args.endpoint == "generate":
        out["tokens_per_second"] = (round(new_tokens[0] / wall, 1)
                                    if wall else None)
    if stream:
        ttfb.sort()
        out["stream"] = True
        out["first_byte_ms"] = {
            "p50": round(_percentile(ttfb, 0.50), 3) if ttfb else None,
            "p95": round(_percentile(ttfb, 0.95), 3) if ttfb else None,
        }
    return out


def scrape_provenance(url):
    _, page = _get(url + "/metrics")
    for line in page.splitlines():
        if line.startswith("# provenance "):
            return json.loads(line[len("# provenance "):]), page
    return None, page


def scrape_value(page, name):
    """Last sample of a counter/gauge on the exposition page (with or
    without the bigdl_serving_ prefix), or None if absent."""
    for line in page.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] in (name,
                                            "bigdl_serving_" + name):
            try:
                return float(parts[1])
            except ValueError:
                return None
    return None


def scrape_quantile(page, name, q):
    """One quantile sample of a registry histogram, e.g.
    ``bigdl_serving_ttft_ms{quantile="0.5"} 12.3`` -> 12.3 (None when
    the line is absent or the histogram is empty/NaN)."""
    needle = f'{name}{{quantile="{q}"}}'
    for line in page.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] in (needle,
                                            "bigdl_serving_" + needle):
            try:
                v = float(parts[1])
            except ValueError:
                return None
            return None if v != v else v  # NaN = empty histogram
    return None


def scrape_server_latency(page):
    """The ISSUE 15 server-side request-latency columns (reqtrace
    histograms): TTFT / TPOT / ITL p50-p99 plus the p50 decomposition
    (queue wait, prefill, decode). All None when the server ran
    --reqTrace off."""
    out = {}
    for name in ("ttft_ms", "tpot_ms", "itl_ms"):
        out[name] = {p: scrape_quantile(page, name, q)
                     for p, q in (("p50", "0.5"), ("p95", "0.95"),
                                  ("p99", "0.99"))}
    for name in ("request_queue_wait_ms", "request_prefill_ms",
                 "request_decode_ms"):
        out[name.replace("request_", "") + "_p50"] = \
            scrape_quantile(page, name, "0.5")
    return out


def scrape_spec_columns(page):
    """The ISSUE 14 speculative-decoding columns: accept rate and tokens
    emitted per target verify step (the dispatch-count win the bench
    reports alongside tokens/s). None-valued when serving --speculate 0.
    """
    return {
        "spec_accept_rate": scrape_value(page, "spec_accept_rate"),
        "accepted_tokens_per_step": scrape_value(
            page, "spec_accepted_tokens_per_step"),
        "decode_steps_total": scrape_value(page, "decode_steps_total"),
        "generated_tokens_total": scrape_value(
            page, "generated_tokens_total"),
    }


def _smoke_latency_agreement(url, args):
    """ISSUE 15 satellite: the server-side TTFT/TPOT histograms
    (reqtrace) must agree with what a client measures from outside.

    Client-side TTFT ~ the round trip of a ``max_new_tokens=1`` generate
    at concurrency 1 (queue wait ~0, one decode round); client-side TPOT
    ~ the per-extra-token slope between a K-token and a 1-token request.
    Tolerances are CPU-CI generous — this catches unit mistakes (s vs
    ms), double counting, and misattributed phases, not microseconds."""
    K = 17
    prompt = list(range(1, 9))
    one, many = [], []
    for _ in range(6):
        t0 = time.perf_counter()
        _post(url + "/generate", {"tokens": prompt, "max_new_tokens": 1})
        one.append((time.perf_counter() - t0) * 1000.0)
        t0 = time.perf_counter()
        _post(url + "/generate", {"tokens": prompt, "max_new_tokens": K})
        many.append((time.perf_counter() - t0) * 1000.0)
    one.sort()
    many.sort()
    ttft_c = _percentile(one, 0.50)
    tpot_c = max((_percentile(many, 0.50) - ttft_c) / (K - 1), 0.0)
    _, page = _get(url + "/metrics")
    ttft_s = scrape_quantile(page, "ttft_ms", "0.5")
    tpot_s = scrape_quantile(page, "tpot_ms", "0.5")
    assert ttft_s is not None and ttft_s > 0, "ttft_ms histogram empty"
    assert tpot_s is not None and tpot_s > 0, "tpot_ms histogram empty"
    assert abs(ttft_s - ttft_c) <= max(100.0, 0.6 * max(ttft_c, ttft_s)), \
        f"TTFT p50 disagree: server {ttft_s:.2f} ms vs client " \
        f"{ttft_c:.2f} ms"
    assert abs(tpot_s - tpot_c) <= max(25.0, 0.6 * max(tpot_c, tpot_s)), \
        f"TPOT p50 disagree: server {tpot_s:.2f} ms vs client " \
        f"{tpot_c:.2f} ms"
    print(f"smoke: server-side p50 agrees with client-side "
          f"(TTFT {ttft_s:.1f}~{ttft_c:.1f} ms, "
          f"TPOT {tpot_s:.2f}~{tpot_c:.2f} ms) OK", flush=True)


def run_smoke(url, args, page_checks=True):
    """Tiny assertion pass: every endpoint answers, metrics count."""
    st, _ = _get(url + "/healthz")
    assert st == 200, f"/healthz -> {st}"
    args.endpoint, args.batch, args.requests = "predict", 2, 4
    args.concurrency = 2
    res = closed_loop(url, args)
    assert res["errors"] == 0, f"predict errors: {res}"
    if args.model.startswith("transformer_lm"):
        args.endpoint = "generate"
        args.promptLen, args.maxNewTokens = 5, 4
        gen = closed_loop(url, args)
        assert gen["errors"] == 0, f"generate errors: {gen}"
        assert gen["tokens_per_second"], gen
    prov, page = scrape_provenance(url)
    assert prov is not None, "metrics page lost its provenance line"
    assert "requests_predict_total" in page
    for needle in ("bn_fused", "autotune", "buckets", "conv_layouts"):
        assert needle in prov, f"provenance missing {needle}: {prov}"
    count = [l for l in page.splitlines()
             if l.startswith("bigdl_serving_requests_predict_total ")]
    assert count and float(count[0].split()[-1]) >= 4, count
    print("smoke: endpoints + metrics provenance OK", flush=True)
    if (args.model.startswith("transformer_lm")
            and prov.get("reqtrace") == "on"):
        _smoke_latency_agreement(url, args)


def run_spec_smoke(args):
    """ISSUE 14 speculative-decoding assertion pass (CI):

    spawn the same tiny LM twice — --speculate 0 and --speculate 4 —
    fire one fixed greedy /generate prompt at each, and assert the
    speculative tokens are BIT-IDENTICAL to the plain ones (the exact-
    acceptance contract), that spec_accept_rate lands non-zero, and
    that the accepted-tokens/step gauge shows >1 token per target
    dispatch (the raw-speed win, observable without a chip as a
    dispatch-count proxy: fewer verify steps than emitted tokens)."""
    prompt = list(range(1, 13))
    body = {"tokens": prompt, "max_new_tokens": 16}
    results = {}
    for k in (0, 4):
        extra = list(args.serveArg) + ["--speculate", str(k)]
        proc, url, log_lines = spawn_server(args, extra)
        try:
            st, out = _post(url + "/generate", body)
            assert st == 200, f"--speculate {k} /generate -> {st}"
            prov, page = scrape_provenance(url)
            assert prov["speculate"] == k, prov
            results[k] = (out["tokens"], scrape_spec_columns(page), prov)
        finally:
            _shutdown_clean(proc, log_lines)
    plain, spec = results[0][0], results[4][0]
    assert spec == plain, (
        f"speculative greedy output diverged:\n  plain {plain}\n"
        f"  spec  {spec}")
    cols = results[4][1]
    assert cols["spec_accept_rate"] and cols["spec_accept_rate"] > 0, cols
    assert cols["accepted_tokens_per_step"] > 1.0, cols
    assert cols["decode_steps_total"] < cols["generated_tokens_total"], \
        cols
    # the measured number also rides the provenance line (scrape-time
    # resolved), next to the static --speculate config
    prov = results[4][2]
    assert prov["spec_accepted_tokens_per_step"] > 1.0, prov
    record = {"bench": "serving_spec_smoke", "prompt_len": len(prompt),
              "max_new_tokens": 16, "bit_identical": True, **cols}
    print(json.dumps(record), flush=True)
    print(f"spec-smoke: --speculate 4 bit-identical, accept_rate="
          f"{cols['spec_accept_rate']:.2f}, accepted-tokens/step="
          f"{cols['accepted_tokens_per_step']:.2f} OK", flush=True)
    return 0


_QUANT_AGREE_MIN = 0.9       # client-side A/B token agreement floor
_QUANT_GUARDRAIL_MIN = 0.98  # server-measured quant_report floor
_QUANT_SLOT_FACTOR = 2.0     # kv8 must admit >= 2x slots at equal HBM


def _kv_slot_capacity(page_tokens=16, max_len=64, dense_slots=8):
    """In-process PagedKvCache A/B at EQUAL pool bytes: size both pools
    to the HBM budget ``dense_slots`` full-length slots cost in f32,
    then count how many slots each variant actually admits via
    ``reserve()`` — the real allocator, not arithmetic."""
    if REPO not in sys.path:  # the spawned servers get cwd=REPO; we
        sys.path.insert(0, REPO)  # import in-process for the allocator
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from bigdl_tpu import models
    from bigdl_tpu.serving.kv_pages import PagedKvCache, pages_needed

    model = models.transformer_lm(64, d_model=32, num_layers=2,
                                  num_heads=2, max_len=max_len)
    per_slot = pages_needed(max_len, page_tokens)

    def probe_bpp(quantized):
        return PagedKvCache(model.encoder, slots=1, max_len=max_len,
                            page_tokens=page_tokens, dtype=jnp.float32,
                            pool_pages=2,
                            quantized=quantized).bytes_per_page

    budget = probe_bpp(False) * per_slot * dense_slots
    out = {}
    for name, quantized in (("off", False), ("int8+kv8", True)):
        bpp = probe_bpp(quantized)
        kv = PagedKvCache(model.encoder, slots=budget // bpp,
                          max_len=max_len, page_tokens=page_tokens,
                          dtype=jnp.float32, pool_pages=budget // bpp,
                          quantized=quantized)
        admitted = 0
        while admitted < kv.slots and kv.reserve(admitted, max_len):
            admitted += 1
        out[name] = {"slots": admitted, "bytes_per_page": int(bpp),
                     "pool_bytes": int(bpp * kv.pool_pages)}
    out["budget_bytes"] = int(budget)
    return out


def run_quant_smoke(args):
    """ISSUE 17 quantized-serving assertion pass (CI quant-smoke leg):

    A/B the same tiny LM under --quantize off and --quantize int8+kv8
    with one fixed greedy /generate prompt. Asserts: the quantized
    output agrees with the full-precision one position-wise at >=
    _QUANT_AGREE_MIN; every server's provenance stamps its quantize
    mode; the quantized server carries the measured quant_report
    guardrail (agreement >= _QUANT_GUARDRAIL_MIN, finite logit error);
    and — the capacity headline — an in-process PagedKvCache A/B at
    EQUAL pool bytes admits >= 2x the slots with 8-bit pools."""
    prompt = list(range(1, 13))
    body = {"tokens": prompt, "max_new_tokens": 16}
    results = {}
    for mode in ("off", "int8+kv8"):
        extra = list(args.serveArg) + ["--quantize", mode]
        proc, url, log_lines = spawn_server(args, extra)
        try:
            st, out = _post(url + "/generate", body)
            assert st == 200, f"--quantize {mode} /generate -> {st}"
            prov, _page = scrape_provenance(url)
            assert prov is not None, "metrics page lost its provenance"
            assert prov.get("quantize") == mode, \
                f"provenance quantize missing/wrong under {mode}: {prov}"
            results[mode] = (out["tokens"], prov)
        finally:
            _shutdown_clean(proc, log_lines)
    base, quant = results["off"][0], results["int8+kv8"][0]
    assert len(base) == len(quant) > 0, (base, quant)
    agree = sum(a == b for a, b in zip(base, quant)) / len(base)
    assert agree >= _QUANT_AGREE_MIN, (
        f"int8+kv8 greedy agreement {agree:.2f} < {_QUANT_AGREE_MIN}:\n"
        f"  off  {base}\n  int8 {quant}")
    qprov = results["int8+kv8"][1]
    assert qprov.get("quant_agreement", 0) >= _QUANT_GUARDRAIL_MIN, qprov
    assert qprov.get("quant_logit_max_err") is not None, qprov
    assert results["off"][1].get("quant_agreement") is None, \
        "off must not pay (or stamp) the quant_report guardrail"
    cap = _kv_slot_capacity()
    factor = cap["int8+kv8"]["slots"] / max(1, cap["off"]["slots"])
    assert factor >= _QUANT_SLOT_FACTOR, (
        f"kv8 admitted only {factor:.2f}x slots at equal HBM: {cap}")
    record = {"bench": "serving_quant_smoke", "prompt_len": len(prompt),
              "max_new_tokens": 16, "agreement": round(agree, 4),
              "quant_agreement": qprov.get("quant_agreement"),
              "quant_logit_max_err": qprov.get("quant_logit_max_err"),
              "slots_off": cap["off"]["slots"],
              "slots_int8_kv8": cap["int8+kv8"]["slots"],
              "slot_factor": round(factor, 2),
              "kv_budget_bytes": cap["budget_bytes"]}
    print(json.dumps(record), flush=True)
    print(f"quant-smoke: int8+kv8 agreement={agree:.2f}, guardrail="
          f"{qprov.get('quant_agreement')}, slots "
          f"{cap['off']['slots']} -> {cap['int8+kv8']['slots']} "
          f"({factor:.1f}x) at equal HBM OK", flush=True)
    return 0


def run_slo_smoke(args):
    """ISSUE 15 assertion pass (CI slo-smoke leg), two servers:

    leg 1 — generous SLO + access log: every request meets the SLO, so
    goodput is 1.0 and violations stay 0; the ttft/tpot histograms
    populate; every response (with and without a client-supplied id)
    echoes ``x-request-id``; a long generation is OBSERVED mid-decode
    through /debug/requests and /debug/slots; after clean shutdown the
    JSONL access log holds exactly one line per completed request;

    leg 2 — unmeetable SLO: every finished request violates, so the
    per-dim violation counters move, burn rate hits 1.0, and once the
    burn window has MIN_BURN_SAMPLES the tiered shedder 429s /generate
    while /predict keeps answering 200."""
    import tempfile
    if not args.model.startswith("transformer_lm"):
        raise SystemExit("--sloSmoke needs --model transformer_lm "
                         "(exercises the decode path)")
    access = os.path.join(tempfile.mkdtemp(prefix="slo_smoke_"),
                          "access.jsonl")

    # ---- leg 1: generous SLO, everything good, in-flight visibility
    proc, url, log_lines = spawn_server(
        args, list(args.serveArg)
        + ["--reqTrace", "on", "--slo", "ttft=60000,tpot=60000",
           "--accessLog", access])
    n_done = 0
    try:
        st, _, hdr = _post_h(url + "/generate",
                             {"tokens": [1, 2, 3, 4],
                              "max_new_tokens": 4},
                             headers={"x-request-id": "slo-smoke-00"})
        assert st == 200, f"/generate -> {st}"
        assert hdr.get("x-request-id") == "slo-smoke-00", \
            f"client request id not echoed: {hdr}"
        n_done += 1
        for _ in range(9):
            st, _, hdr = _post_h(url + "/generate",
                                 {"tokens": [5, 6, 7, 8],
                                  "max_new_tokens": 6})
            assert st == 200, f"/generate -> {st}"
            assert hdr.get("x-request-id"), f"no minted rid echoed: {hdr}"
            n_done += 1

        # in-flight visibility: long generations polled mid-decode
        fired, seen_decode, seen_slots = [0], False, False
        def _long():
            fired[0] += 1
            _post_status(url + "/generate",
                         {"tokens": [9, 10, 11, 12],
                          "max_new_tokens": 48}, timeout=120)
        deadline = time.time() + 60
        while time.time() < deadline and not (seen_decode and seen_slots):
            threads = [threading.Thread(target=_long) for _ in range(2)]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                st, txt = _get_status(url + "/debug/requests")
                assert st == 200, f"/debug/requests -> {st}"
                snap = json.loads(txt)
                assert snap.get("enabled") is True, snap
                for r in snap.get("in_flight", []):
                    if (r.get("state") == "decode"
                            and r.get("tokens_out", 0) > 0):
                        seen_decode = True
                st, txt = _get_status(url + "/debug/slots")
                assert st == 200, f"/debug/slots -> {st}"
                slots = json.loads(txt)
                for k in ("slots", "slots_total", "slots_active",
                          "waiting", "kv"):
                    assert k in slots, f"/debug/slots missing {k}: {slots}"
                if slots.get("slots_active", 0) >= 1:
                    seen_slots = True
            for t in threads:
                t.join()
        assert seen_decode, "/debug/requests never showed a request " \
                            "mid-decode (state=decode, tokens_out>0)"
        assert seen_slots, "/debug/slots never showed an active slot"
        n_done += fired[0]

        _, page = _get(url + "/metrics")
        for name in ("ttft_ms", "tpot_ms", "itl_ms"):
            q = scrape_quantile(page, name, "0.5")
            assert q is not None and q > 0, \
                f"{name} histogram not populated"
        total = scrape_value(page, "slo_requests_total")
        good = scrape_value(page, "slo_good_total")
        viol = scrape_value(page, "slo_violations_total")
        assert total == n_done, (total, n_done)
        assert good == total and viol == 0, (good, viol, total)
        assert scrape_value(page, "slo_goodput_frac") == 1.0
        assert scrape_value(page, "requests_state_finished_total") \
            == n_done
        print(f"slo-smoke leg 1: {n_done} requests all good, goodput "
              f"1.0, mid-decode visible via /debug/* OK", flush=True)
    finally:
        _shutdown_clean(proc, log_lines)

    with open(access) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    assert len(recs) == n_done, \
        f"access log has {len(recs)} lines, expected {n_done}"
    rids = [r["rid"] for r in recs]
    assert len(set(rids)) == len(rids), "duplicate rids in access log"
    assert "slo-smoke-00" in rids, rids
    for r in recs:
        for k in ("rid", "endpoint", "state", "status", "ttft_ms",
                  "tpot_ms", "queue_wait_ms", "prefill_ms", "decode_ms",
                  "total_ms", "tokens_out"):
            assert k in r, f"access-log line missing {k}: {r}"
        assert r["state"] == "finished" and r["status"] == 200, r
    print(f"slo-smoke: access log {len(recs)}/{n_done} lines, "
          f"unique rids OK", flush=True)

    # ---- leg 2: unmeetable SLO -> violations, burn, tiered shed
    proc, url, log_lines = spawn_server(
        args, list(args.serveArg)
        + ["--slo", "ttft=0.001,tpot=0.001,burn=0.5,window=16"])
    try:
        statuses = []
        for _ in range(14):
            st, _, hdr = _post_h(url + "/generate",
                                 {"tokens": [1, 2, 3],
                                  "max_new_tokens": 4})
            assert hdr.get("x-request-id"), hdr
            statuses.append(st)
        # burn gate: no shedding below MIN_BURN_SAMPLES finished requests
        assert all(s == 200 for s in statuses[:8]), statuses
        assert 429 in statuses, \
            f"SLO burn never tripped the shedder: {statuses}"
        assert statuses[-1] == 429, statuses
        args.endpoint, args.batch = "predict", 1
        st, _, _ = _post_h(url + "/predict", make_payload(args))
        assert st == 200, f"/predict under SLO shed -> {st} (tiered " \
                          "shed must spare predict)"
        _, page = _get(url + "/metrics")
        assert scrape_value(page, "slo_violations_total") >= 8
        assert scrape_value(page, "slo_ttft_violations_total") >= 8
        assert scrape_value(page, "slo_burn_rate") == 1.0
        assert scrape_value(page, "requests_state_shed_total") >= 1
        st, txt = _get_status(url + "/debug/requests")
        assert st == 200 and json.loads(txt)["slo"]["shedding"] is True
        print(f"slo-smoke leg 2: {statuses.count(429)} shed by SLO "
              f"burn, predict spared OK", flush=True)
    finally:
        _shutdown_clean(proc, log_lines)
    print("slo-smoke: all ISSUE 15 assertions OK", flush=True)
    return 0


def scrape_labelled(page, name, label="replica"):
    """All samples of a replica-labelled gauge/counter on the exposition
    page, keyed by label value — e.g. ``decode_worker_up{replica="1"} 1``
    -> {"1": 1.0}. Tolerates the bigdl_serving_ namespace prefix."""
    pat = re.compile(r'^(?:bigdl_serving_)?%s\{%s="([^"]+)"\} (\S+)$'
                     % (re.escape(name), re.escape(label)))
    out = {}
    for line in page.splitlines():
        m = pat.match(line)
        if m:
            try:
                out[m.group(1)] = float(m.group(2))
            except ValueError:
                pass
    return out


def run_tp_smoke(args):
    """ISSUE 16 multi-chip serving assertion pass (CI serving-tp-smoke):

    leg 1 — tensor parallel: the same tiny LM is served single-chip and
    --strategy tp:2 (virtual devices), both with speculative decoding,
    paged KV, and the prefix cache ON; a fixed greedy prompt, an exact
    repeat of it (prefix-cache page-copy hit), and a second prompt
    sharing its prefix must all come back BIT-IDENTICAL across the two
    topologies — sharding must never change which token argmax wins;

    leg 2 — data parallel: --strategy dp:2 brings two full engine
    stacks up behind one port; /readyz counts both live, /metrics
    carries per-replica labelled worker gauges AND the unlabelled fleet
    aggregates, routed requests come back replica-stamped in
    /debug/requests, and one SIGTERM takes the whole fleet down rc=0."""
    shared = list(range(1, 17))  # one full page at --kvPageTokens 16
    bodies = [
        {"tokens": shared + [21, 22], "max_new_tokens": 12},
        {"tokens": shared + [21, 22], "max_new_tokens": 12},  # prefix hit
        {"tokens": shared + [33, 34, 35], "max_new_tokens": 12},
    ]
    tp_extra = ["--kvPageTokens", "16", "--prefixCache",
                "--speculate", "3"]
    results = {}
    for strat in (None, "tp:2"):
        extra = list(args.serveArg) + tp_extra
        if strat:
            extra += ["--strategy", strat]
        proc, url, log_lines = spawn_server(args, extra)
        try:
            outs = []
            for body in bodies:
                st, out = _post(url + "/generate", body)
                assert st == 200, f"{strat or 'single'} /generate -> {st}"
                outs.append(out["tokens"])
            prov, page = scrape_provenance(url)
            results[strat] = (outs, prov)
        finally:
            _shutdown_clean(proc, log_lines)
    single, tp = results[None][0], results["tp:2"][0]
    for i, (a, b) in enumerate(zip(single, tp)):
        assert a == b, (f"tp:2 output diverged from single-chip on "
                        f"prompt {i}:\n  single {a}\n  tp:2   {b}")
    prov = results["tp:2"][1]
    assert prov.get("strategy") == "tp:2", prov
    assert prov.get("serving_tp") == 2, prov
    assert prov.get("serving_replicas") == 1, prov
    assert prov.get("n_devices", 0) >= 2, prov
    print(f"tp-smoke: tp:2 bit-identical to single-chip on "
          f"{len(bodies)} prompts (spec+paged+prefix-cache on) OK",
          flush=True)

    # ---- leg 2: dp:2 — fleet readiness, labelled + aggregate metrics
    proc, url, log_lines = spawn_server(
        args, list(args.serveArg)
        + ["--strategy", "dp:2", "--reqTrace", "on"])
    try:
        st, txt = _get(url + "/readyz")
        assert st == 200, f"/readyz -> {st}"
        ready = json.loads(txt)
        assert ready.get("replicas") == 2, ready
        assert ready.get("replicas_live") == 2, ready
        errs = [0]

        def _fire():
            st, _ = _post_status(url + "/generate",
                                 {"tokens": [1, 2, 3, 4, 5],
                                  "max_new_tokens": 6}, timeout=120)
            if st != 200:
                errs[0] += 1
        threads = [threading.Thread(target=_fire) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs[0] == 0, f"{errs[0]}/6 dp:2 generates failed"
        prov, page = scrape_provenance(url)
        assert prov.get("strategy") == "dp:2", prov
        assert prov.get("serving_replicas") == 2, prov
        up = scrape_labelled(page, "decode_worker_up")
        assert up.get("0") == 1.0 and up.get("1") == 1.0, \
            f"per-replica decode_worker_up gauges missing/down: {up}"
        assert scrape_value(page, "replicas") == 2, "no fleet gauge"
        assert scrape_value(page, "replicas_live") == 2, page[:200]
        for agg in ("kv_cache_bytes", "kv_pages_in_use",
                    "fleet_generated_tokens_total"):
            assert scrape_value(page, agg) is not None, \
                f"aggregate {agg} gauge missing"
        per_rep_tokens = scrape_labelled(page, "generated_tokens_total")
        assert sum(per_rep_tokens.values()) >= 6, per_rep_tokens
        st, txt = _get(url + "/debug/requests")
        assert st == 200, st
        recent = json.loads(txt).get("recent", [])
        stamped = [r for r in recent if "replica" in r]
        assert stamped, f"no replica-stamped records: {recent}"
        assert all(r["replica"] in (0, 1) for r in stamped), stamped
        print(f"tp-smoke: dp:2 fleet live, labelled+aggregate metrics, "
              f"{len(stamped)} replica-stamped records OK", flush=True)
    finally:
        _shutdown_clean(proc, log_lines)
    record = {"bench": "serving_tp_smoke", "bit_identical": True,
              "tp": 2, "dp_replicas": 2, "prompts": len(bodies)}
    print(json.dumps(record), flush=True)
    print("tp-smoke: all ISSUE 16 multi-chip assertions OK", flush=True)
    return 0


def run_dp_sweep(args):
    """dp QPS scaling sweep (the ISSUE 16 perf headline): run the same
    closed-loop /generate load against ``--strategy dp:N`` for each N
    in --dpSweep and report aggregate client-side QPS against the
    linear ideal (N x the per-replica rate of the first point). Each
    record carries the server's provenance and the per-replica
    generated-token split so the routing spread is visible.

    --assertScaling F turns the floor into a hard assertion
    (aggregate QPS >= F x linear at every N). Use that on real chips;
    virtual CPU devices share the same host cores, so CPU CI reports
    the curve without asserting it."""
    counts = [int(x) for x in args.dpSweep.split(",") if x]
    assert counts, "--dpSweep needs at least one replica count"
    args.endpoint = "generate"
    base_conc = args.concurrency
    records = []
    for n in counts:
        extra = list(args.serveArg) + ["--strategy", f"dp:{n}"]
        proc, url, log_lines = spawn_server(args, extra)
        # keep every replica busy: concurrency scales with the fleet
        args.concurrency = max(base_conc, 4 * n)
        try:
            res = closed_loop(url, args)
            assert res["errors"] == 0, f"dp:{n} bench errors: {res}"
            prov, page = scrape_provenance(url)
            assert prov.get("serving_replicas") == n, prov
            rec = {"bench": "serving_dp_sweep", "replicas": n,
                   "qps": res["rps"],
                   "tokens_per_second": res["tokens_per_second"],
                   "concurrency": args.concurrency,
                   "requests": args.requests,
                   "latency_ms": res["latency_ms"],
                   "per_replica_tokens": scrape_labelled(
                       page, "generated_tokens_total"),
                   "provenance": prov}
        finally:
            args.concurrency = base_conc
            _shutdown_clean(proc, log_lines)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    per_replica0 = records[0]["qps"] / records[0]["replicas"]
    summary = {"bench": "serving_dp_sweep_summary",
               "counts": counts,
               "qps": [r["qps"] for r in records],
               "scaling_vs_linear": [
                   round(r["qps"] / (per_replica0 * r["replicas"]), 3)
                   for r in records]}
    print(json.dumps(summary), flush=True)
    if args.assertScaling is not None:
        floor = args.assertScaling
        for n, frac in zip(counts, summary["scaling_vs_linear"]):
            assert frac >= floor, \
                (f"dp:{n} aggregate QPS is {frac:.2f}x linear, below "
                 f"the {floor}x floor")
        print(f"dp-sweep: all points >= {floor}x linear OK", flush=True)
    return 0


def _companion_keys():
    """The shared provenance companion-key list (cli/provenance.py),
    loaded by file path so the bench parent never imports the bigdl_tpu
    package (whose import pulls jax: a parent that has touched jax
    holds the chip, and a child that needs it then fails or hangs).
    """
    import importlib.util
    path = os.path.join(REPO, "bigdl_tpu", "cli", "provenance.py")
    try:
        spec = importlib.util.spec_from_file_location("_sb_prov", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return tuple(mod.PROVENANCE_COMPANION_KEYS)
    except Exception:
        return ("conv_layouts", "conv_geom", "autotune", "bn_fused",
                "pipeline", "stall_frac", "data_wait_s")


def _stream_generate(url, body, read_frames=None, timeout=120.0):
    """POST /generate with ``stream: true`` and parse the SSE frames off
    the chunked response. Returns ``(status, frames, t_first_byte_s,
    t_done_s, conn)`` — when ``read_frames`` is set, returns after that
    many token frames WITHOUT closing the connection (``conn`` is live;
    the disconnect leg closes it mid-decode)."""
    import http.client
    from urllib.parse import urlparse
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    t0 = time.perf_counter()
    conn.request("POST", "/generate",
                 json.dumps({**body, "stream": True}).encode(),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        try:
            out = json.loads(resp.read() or b"{}")
        except ValueError:
            out = {}
        conn.close()
        return resp.status, out, None, None, None
    assert resp.getheader("Content-Type", "").startswith(
        "text/event-stream"), resp.getheader("Content-Type")
    frames, t_first, buf = [], None, b""
    while True:
        b1 = resp.read(1)  # http.client undoes the chunked framing
        if not b1:
            break
        if t_first is None:
            t_first = time.perf_counter() - t0
        buf += b1
        while b"\n\n" in buf:
            raw, buf = buf.split(b"\n\n", 1)
            if raw.startswith(b"data: "):
                frames.append(json.loads(raw[len(b"data: "):]))
        if read_frames is not None and len(
                [f for f in frames if "tokens" in f]) >= read_frames:
            return resp.status, frames, t_first, None, conn
        if frames and frames[-1].get("done"):
            break
    t_done = time.perf_counter() - t0
    conn.close()
    return resp.status, frames, t_first, t_done, None


def run_stream_smoke(args):
    """ISSUE 18 streaming assertion pass (CI throughput-smoke leg), one
    server with the full composition on — speculative decoding, paged
    KV, lifecycle tracing, SLOs:

    leg 1 — bit-identity: for >= 3 fixed greedy prompts the streamed
    token frames, concatenated, equal the buffered /generate response
    exactly (speculative path included: only ACCEPTED tokens are ever
    emitted), and the final frame carries done/prompt_len/tokens_out;

    leg 2 — felt TTFT: the first SSE byte lands well before the
    buffered response for the same prompt completes, and the
    server-side ttft_ms histogram (stamped at first-byte-out, feeding
    --slo) is populated;

    leg 3 — disconnect: a client that walks away mid-stream gets its
    slot cancelled — decode_cancelled_total moves, kv_pages_in_use
    returns to the pre-request baseline (no leaked page reservations),
    and the request lands terminal state ``closed`` in /debug/requests.
    """
    extra = (list(args.serveArg)
             + ["--kvPageTokens", "16", "--speculate", "3",
                "--reqTrace", "on", "--slo", "ttft=60000,tpot=60000"])
    prompts = [list(range(1, 9)), list(range(5, 21)),
               [2, 3, 5, 7, 11, 13]]
    proc, url, log_lines = spawn_server(args, extra)
    try:
        # ---- leg 1: streamed == buffered, per prompt, bit for bit
        first_ms = full_ms = None
        for i, prompt in enumerate(prompts):
            body = {"tokens": prompt, "max_new_tokens": 24,
                    "temperature": 0.0}
            t0 = time.perf_counter()
            st, ref = _post(url + "/generate", body)
            buffered_s = time.perf_counter() - t0
            assert st == 200, f"buffered /generate -> {st}"
            st, frames, t_first, t_done, _ = _stream_generate(url, body)
            assert st == 200, f"streamed /generate -> {st}"
            toks = [t for f in frames if "tokens" in f
                    for t in f["tokens"]]
            assert toks == ref["tokens"], (
                f"streamed output diverged on prompt {i}:\n"
                f"  buffered {ref['tokens']}\n  streamed {toks}")
            final = frames[-1]
            assert final.get("done") is True, final
            assert final.get("prompt_len") == len(prompt), final
            assert final.get("tokens_out") == len(toks), final
            if i == 0:
                # ---- leg 2: first byte beats the full round trip
                assert t_first < t_done, (t_first, t_done)
                assert t_first < buffered_s, (
                    f"first SSE byte ({t_first * 1000:.1f} ms) not ahead "
                    f"of the buffered response ({buffered_s * 1000:.1f} "
                    f"ms)")
                first_ms = round(t_first * 1000, 2)
                full_ms = round(buffered_s * 1000, 2)
        _, page = _get(url + "/metrics")
        ttft = scrape_quantile(page, "ttft_ms", "0.5")
        assert ttft is not None and ttft > 0, \
            "ttft_ms histogram empty — first-byte stamp not feeding SLOs"
        print(f"stream-smoke: {len(prompts)} prompts bit-identical "
              f"(speculate on), first byte {first_ms} ms vs buffered "
              f"{full_ms} ms, ttft_ms populated OK", flush=True)

        # ---- leg 3: mid-stream disconnect frees the slot + pages
        _, page = _get(url + "/metrics")
        base_pages = scrape_value(page, "kv_pages_in_use") or 0
        st, frames, _, _, conn = _stream_generate(
            url, {"tokens": list(range(1, 9)), "max_new_tokens": 48,
                  "temperature": 0.0}, read_frames=1)
        assert st == 200 and conn is not None, (st, frames)
        conn.close()  # walk away mid-decode
        deadline = time.time() + 60
        cancelled = pages_ok = False
        while time.time() < deadline:
            _, page = _get(url + "/metrics")
            cancelled = (scrape_value(page,
                                      "decode_cancelled_total") or 0) >= 1
            pages_ok = (scrape_value(page, "kv_pages_in_use")
                        or 0) <= base_pages
            if cancelled and pages_ok:
                break
            time.sleep(0.2)
        assert cancelled, "decode_cancelled_total never moved after " \
                          "client disconnect"
        assert pages_ok, "kv_pages_in_use never returned to baseline " \
                         "(leaked page reservations)"
        st, txt = _get_status(url + "/debug/requests")
        assert st == 200, st
        recent = json.loads(txt).get("recent", [])
        closed = [r for r in recent if r.get("state") == "closed"]
        assert closed, f"no terminal-state closed record: {recent}"
        # a fresh request still runs on the freed slot
        st, out = _post(url + "/generate",
                        {"tokens": [1, 2, 3], "max_new_tokens": 4})
        assert st == 200 and out["tokens"], (st, out)
        print("stream-smoke: disconnect cancelled mid-decode, pages "
              "freed, state=closed, slot reusable OK", flush=True)

        prov, _ = scrape_provenance(url)
        record = {"bench": "serving_stream_smoke",
                  "prompts": len(prompts), "bit_identical": True,
                  "first_byte_ms": first_ms, "buffered_ms": full_ms,
                  "server_ttft_p50_ms": ttft,
                  "disconnect_freed_pages": True,
                  **{k: prov[k] for k in _companion_keys()
                     if k in (prov or {})}}
        print(json.dumps(record), flush=True)
    finally:
        _shutdown_clean(proc, log_lines)
    print("stream-smoke: all ISSUE 18 streaming assertions OK",
          flush=True)
    return 0


def _shutdown_clean(proc, log_lines):
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise SystemExit("server ignored SIGTERM")
    assert rc == 0, f"server exit code {rc} after SIGTERM"
    assert any("serving shutdown clean" in l for l in log_lines), \
        "missing clean-shutdown marker in server log"


def run_chaos_smoke(args):
    """ISSUE 6 serving-hardening assertions (CI chaos-smoke job):

    leg 1 — deadline expiry: a request with deadline_ms=0 is dropped
    BEFORE compute and answered 504 (distinct from admission 429),
    while normal requests keep answering 200;

    leg 2 — worker kill: a --faultPlan kills the batcher worker on its
    2nd flush; the in-flight request errors (500), the NEXT submit
    fast-fails 503 in well under a second (no hanging until client
    timeout), /readyz flips 503 while /healthz stays 200, the fault
    counters land in /metrics, and SIGTERM still shuts down rc=0."""
    rng_payload = make_payload(args)

    # ---- leg 1: deadline expiry -> 504, healthy path unaffected
    proc, url, log_lines = spawn_server(args, list(args.serveArg))
    try:
        st, _ = _post_status(url + "/predict", rng_payload)
        assert st == 200, f"healthy predict -> {st}"
        st, body = _post_status(url + "/predict",
                                {**rng_payload, "deadline_ms": 0})
        assert st == 504, f"expired-deadline predict -> {st} ({body})"
        assert "deadline" in body.get("error", ""), body
        st, _ = _post_status(url + "/predict", rng_payload)
        assert st == 200, f"predict after 504 -> {st}"
        st, _ = _get_status(url + "/readyz")
        assert st == 200, f"/readyz (healthy) -> {st}"
        print("chaos-smoke: deadline expiry -> 504, healthy path OK",
              flush=True)
    finally:
        _shutdown_clean(proc, log_lines)

    # ---- leg 2: worker kill -> fast 503 + readiness flip
    proc, url, log_lines = spawn_server(
        args, list(args.serveArg)
        + ["--faultPlan", "worker_kill@infer:2", "--watchdogStallS", "5"])
    try:
        st, _ = _post_status(url + "/predict", rng_payload)
        assert st == 200, f"predict before kill -> {st}"
        st, body = _post_status(url + "/predict", rng_payload)
        assert st == 500, f"killed-flush predict -> {st} ({body})"
        t0 = time.perf_counter()
        st, body = _post_status(url + "/predict", rng_payload)
        dt = time.perf_counter() - t0
        assert st == 503, f"post-kill predict -> {st} ({body})"
        assert dt < 2.0, f"dead-worker 503 took {dt:.2f}s (not fast)"
        st, _ = _get_status(url + "/readyz")
        assert st == 503, f"/readyz (dead worker) -> {st}"
        st, _ = _get_status(url + "/healthz")
        assert st == 200, f"/healthz must stay live, got {st}"
        _, page = _get(url + "/metrics")
        for needle in ("batcher_worker_up 0",
                       "requests_worker_dead_total"):
            assert needle in page, f"metrics missing {needle!r}"
        print(f"chaos-smoke: worker kill -> 500 then fast 503 "
              f"({dt * 1000:.0f} ms), /readyz 503, /healthz 200 OK",
              flush=True)
    finally:
        _shutdown_clean(proc, log_lines)

    # ---- leg 3 (ISSUE 20 satellite): rids must survive the router —
    # 5xx responses produced BEHIND a proxy hop (and by the router
    # itself once every worker is gone) still echo x-request-id
    proc, url, log_lines = spawn_fleet(
        args, list(args.serveArg)
        + ["--faultPlan", "worker_kill@infer:2", "--watchdogStallS", "5",
           "--fleetRestartBudget", "0"], k=1)
    try:
        st, _, hdr = _post_h(url + "/predict", rng_payload,
                             headers={"x-request-id": "chaos-hop-00"})
        assert st == 200, f"fleet predict -> {st}"
        assert hdr.get("x-request-id") == "chaos-hop-00", hdr
        # deadline expiry 504 answered by the WORKER, relayed by the
        # router (dropped before compute, so no infer flush is spent)
        st, body, hdr = _post_h(url + "/predict",
                                {**rng_payload, "deadline_ms": 0},
                                headers={"x-request-id": "chaos-hop-04"})
        assert st == 504, f"proxied expired-deadline -> {st} ({body})"
        assert hdr.get("x-request-id") == "chaos-hop-04", \
            f"rid lost on proxied 504: {hdr}"
        # 2nd infer flush kills the batcher worker thread: 500 then a
        # fast 503, both proxied, both rid-stamped
        st, body, hdr = _post_h(url + "/predict", rng_payload,
                                headers={"x-request-id": "chaos-hop-05"})
        assert st == 500, f"proxied killed-flush -> {st} ({body})"
        assert hdr.get("x-request-id") == "chaos-hop-05", \
            f"rid lost on proxied 500: {hdr}"
        st, body, hdr = _post_h(url + "/predict", rng_payload,
                                headers={"x-request-id": "chaos-hop-03"})
        assert st == 503, f"proxied dead-worker -> {st} ({body})"
        assert hdr.get("x-request-id") == "chaos-hop-03", \
            f"rid lost on proxied 503: {hdr}"
        # now remove the PROCESS: restart budget 0 means the router
        # gives the slot up, and its OWN no-live-worker 503 (and the
        # /readyz flip) must still carry the rid
        st, body = _get(url + "/debug/fleet")
        pid = json.loads(body)["workers"][0]["pid"]
        os.kill(pid, signal.SIGKILL)
        deadline = time.time() + 30
        while time.time() < deadline:
            st, body, hdr = _post_h(url + "/predict", rng_payload,
                                    headers={"x-request-id":
                                             "chaos-hop-99"})
            assert hdr.get("x-request-id") == "chaos-hop-99", \
                f"rid lost on router {st}: {hdr}"
            if st == 503 and "no live fleet worker" in \
                    body.get("error", ""):
                break
            time.sleep(0.5)
        else:
            raise AssertionError("router never originated its own 503")
        st, _ = _get_status(url + "/readyz")
        assert st == 503, f"/readyz with zero workers -> {st}"
        print("chaos-smoke: x-request-id survives the proxy hop on "
              "504/500/503 + router-originated 503 OK", flush=True)
    finally:
        _shutdown_clean(proc, log_lines)
    print("chaos-smoke: all serving-hardening assertions OK", flush=True)
    return 0


def spawn_fleet(args, extra, k=2):
    """Launch `bigdl-tpu serve --fleet K` (the ISSUE 20 router + K
    worker processes) on an ephemeral port. Same contract as
    spawn_server, but the port is parsed from the ROUTER's banner —
    worker banners arrive first, prefixed ``[worker N]``, and must not
    win."""
    cmd = [sys.executable, "-m", "bigdl_tpu.cli.main", "serve",
           args.model, "--port", "0", "--fleet", str(k)]
    if args.ckpt:
        cmd += ["--model", args.ckpt]
    else:
        cmd += ["--randomInit"]
    if args.platform:
        cmd += ["--platform", args.platform]
    if args.model.startswith("transformer_lm") and (args.smoke
                                                    or not args.ckpt):
        cmd += _SMOKE_LM
    cmd += extra
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines, port = [], None
    port_re = re.compile(r"^serving .+ fleet on http://[^:]+:(\d+)")
    ready = threading.Event()

    def _reader():
        nonlocal port
        for line in proc.stdout:
            lines.append(line.rstrip())
            m = port_re.match(lines[-1])
            if m:
                port = int(m.group(1))
                ready.set()
        ready.set()

    threading.Thread(target=_reader, daemon=True).start()
    if not ready.wait(timeout=600) or port is None:
        proc.kill()
        raise SystemExit("fleet router never reported its port; log "
                         "tail:\n" + "\n".join(lines[-30:]))
    return proc, f"http://127.0.0.1:{port}", lines


def _make_lm_ckpt(path, seed=42):
    """A version-stamped smoke-LM checkpoint (same dims as _SMOKE_LM)
    for the rolling-swap leg — different seed, visibly different
    weights. Built on the CPU: this parent must never claim the chip its
    worker children need (one process per chip)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu import models
    from bigdl_tpu.utils.file import save_pytree
    m = models.transformer_lm(64, d_model=32, num_layers=2, num_heads=2,
                              max_len=64)
    save_pytree({"params": m.init(jax.random.PRNGKey(seed)),
                 "mod_state": m.init_state()},
                os.path.join(path, "model.1"))
    return path


def run_fleet_smoke(args):
    """ISSUE 20 fleet assertions (CI fleet-smoke job), one K=2 fleet:

    leg 1 — the router front door: /generate proxied with the client
    rid echoed and x-model-version stamped; /metrics carries the
    router's bigdl_fleet series plus worker-labelled re-exports and
    summed aggregates; /readyz 200.

    leg 2 — elasticity: kill -9 one worker; /readyz stays 200 and
    /generate keeps answering on the survivor throughout; the killed
    worker is restarted within the supervisor budget and rejoins
    rotation (restarts >= 1, routable again).

    leg 3 — zero-downtime rolling swap: under continuous traffic, POST
    /admin/reload to a version-B checkpoint; every response during the
    swap is 200 (no 5xx window), both versions are observed across the
    window, and afterwards every response reports vB."""
    import tempfile

    ckpt_b = _make_lm_ckpt(os.path.join(
        tempfile.mkdtemp(prefix="fleet_smoke_"), "ck_vB"))
    proc, url, log_lines = spawn_fleet(
        args, list(args.serveArg) + ["--modelVersion", "vA"], k=2)
    gen = {"tokens": [3, 1, 4], "max_new_tokens": 4}
    try:
        # ---- leg 1: router basics
        st, _, hdr = _post_h(url + "/generate", gen,
                             headers={"x-request-id": "fleet-smoke-00"})
        assert st == 200, f"proxied generate -> {st}"
        assert hdr.get("x-request-id") == "fleet-smoke-00", hdr
        assert hdr.get("x-model-version") == "vA", hdr
        st, _ = _get_status(url + "/readyz")
        assert st == 200, f"/readyz -> {st}"
        _, page = _get(url + "/metrics")
        for needle in ("bigdl_fleet_workers 2",
                       "bigdl_fleet_requests_generate_total",
                       "# fleet aggregate", 'worker="0"', 'worker="1"'):
            assert needle in page, f"fleet metrics missing {needle!r}"
        print("fleet-smoke: router proxy + rid/version echo + "
              "aggregated metrics OK", flush=True)

        # ---- leg 2: kill one worker; serve through it, expect rejoin
        _, body = _get(url + "/debug/fleet")
        pid = json.loads(body)["workers"][0]["pid"]
        os.kill(pid, signal.SIGKILL)
        deadline = time.time() + 120
        rejoined = False
        while time.time() < deadline:
            st, _ = _get_status(url + "/readyz")
            assert st == 200, "/readyz flipped 503 with a live survivor"
            st, _, _ = _post_h(url + "/generate", gen, timeout=60)
            assert st == 200, f"generate during restart -> {st}"
            _, body = _get(url + "/debug/fleet")
            w0 = json.loads(body)["workers"][0]
            if w0["routable"] and w0["restarts"] >= 1:
                rejoined = True
                break
            time.sleep(1.0)
        assert rejoined, "killed worker never rejoined rotation"
        print("fleet-smoke: kill -9 -> restart + rejoin, /readyz 200 "
              "throughout OK", flush=True)

        # ---- leg 3: rolling swap under traffic, zero 5xx window
        results = []
        stop = threading.Event()

        def _traffic():
            while not stop.is_set():
                s, _, h = _post_h(url + "/generate", gen, timeout=60)
                results.append((s, h.get("x-model-version")))
                time.sleep(0.05)

        t = threading.Thread(target=_traffic, daemon=True)
        t.start()
        time.sleep(1.0)
        st, body, _ = _post_h(url + "/admin/reload",
                              {"checkpoint": ckpt_b, "version": "vB"},
                              timeout=600)
        assert st == 200, f"/admin/reload -> {st} ({body})"
        assert all(r["status"] == "reloaded" for r in body["workers"]), \
            body
        time.sleep(1.0)
        stop.set()
        t.join(60)
        statuses = sorted({s for s, _ in results})
        versions = sorted({v for _, v in results})
        assert statuses == [200], \
            f"5xx window during rolling swap: {statuses}"
        assert versions == ["vA", "vB"], \
            f"expected both versions across the swap, saw {versions}"
        st, _, hdr = _post_h(url + "/generate", gen)
        assert st == 200 and hdr.get("x-model-version") == "vB", hdr
        record = {"bench": "serving_fleet_smoke", "workers": 2,
                  "swap_requests": len(results), "swap_5xx": 0,
                  "versions_observed": versions}
        print(json.dumps(record), flush=True)
        print(f"fleet-smoke: rolling swap vA->vB with zero 5xx over "
              f"{len(results)} in-flight requests OK", flush=True)
    finally:
        _shutdown_clean(proc, log_lines)
    print("fleet-smoke: all ISSUE 20 fleet assertions OK", flush=True)
    return 0


def main(argv=None):
    p = argparse.ArgumentParser("serving_bench")
    p.add_argument("--model", default="lenet5",
                   help="perf-zoo name (payload geometry + spawn target)")
    p.add_argument("--url", default=None,
                   help="bench an already-running server instead of "
                        "spawning one")
    p.add_argument("--ckpt", default=None,
                   help="checkpoint for the spawned server (default "
                        "--randomInit)")
    p.add_argument("--endpoint", default="predict",
                   choices=["predict", "generate"])
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--batch", type=int, default=1,
                   help="rows per /predict request")
    p.add_argument("--promptLen", type=int, default=16)
    p.add_argument("--maxNewTokens", type=int, default=16)
    p.add_argument("--stream", action="store_true",
                   help="drive the load through the chunked-SSE "
                        "/generate path instead of buffered responses; "
                        "adds client-side first_byte_ms percentiles to "
                        "the JSON line (the streamed half of the "
                        "streamed-vs-buffered TTFT/TPOT A/B)")
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    p.add_argument("--smoke", action="store_true",
                   help="assertion pass + clean-shutdown check (CI)")
    p.add_argument("--specSmoke", action="store_true",
                   help="speculative-decoding assertion pass (ISSUE 14):"
                        " --speculate 4 /generate bit-identical to "
                        "--speculate 0, non-zero accept rate, >1 "
                        "accepted-tokens/step (spawns its own servers)")
    p.add_argument("--quantSmoke", action="store_true",
                   help="quantized-serving assertion pass (ISSUE 17): "
                        "--quantize int8+kv8 /generate agrees with "
                        "--quantize off, quantize + measured guardrail "
                        "stamped in provenance, and 8-bit KV pools "
                        "admit >= 2x the slots at equal pool bytes "
                        "(spawns its own servers)")
    p.add_argument("--sloSmoke", action="store_true",
                   help="per-request observability assertion pass "
                        "(ISSUE 15): TTFT/TPOT histograms populate, "
                        "goodput/violation counters move, SLO burn "
                        "trips the tiered shedder (generate 429s, "
                        "predict spared), one access-log line per "
                        "request, x-request-id echoed, /debug/requests "
                        "shows requests mid-decode (spawns its own "
                        "servers)")
    p.add_argument("--chaosSmoke", action="store_true",
                   help="serving-hardening assertion pass (ISSUE 6): "
                        "deadline-expiry 504, worker-kill fast 503 + "
                        "watchdog readiness flip, and x-request-id "
                        "echo on 503/504s routed through a fleet "
                        "proxy hop (spawns its own servers)")
    p.add_argument("--fleetSmoke", action="store_true",
                   help="serving-fleet assertion pass (ISSUE 20): "
                        "2-worker fleet behind the router — proxied "
                        "rid/version echo, worker-labelled + summed "
                        "/metrics, kill -9 restart/rejoin with /readyz "
                        "200 throughout, and a rolling /admin/reload "
                        "with zero 5xx while both x-model-versions are "
                        "observed (spawns its own fleet)")
    p.add_argument("--streamSmoke", action="store_true",
                   help="streaming /generate assertion pass (ISSUE 18): "
                        "streamed SSE tokens bit-identical to buffered "
                        "(speculate+paged KV on), first byte ahead of "
                        "the buffered round trip with ttft_ms fed at "
                        "first-byte-out, and a mid-stream disconnect "
                        "cancels the slot + frees KV pages with "
                        "terminal state closed (spawns its own server)")
    p.add_argument("--tpSmoke", action="store_true",
                   help="multi-chip serving assertion pass (ISSUE 16): "
                        "--strategy tp:2 /generate bit-identical to "
                        "single-chip (speculate + paged KV + prefix "
                        "cache on), dp:2 fleet readiness + per-replica "
                        "labelled metrics + aggregates + replica-"
                        "stamped traces (spawns its own servers on "
                        "virtual devices)")
    p.add_argument("--dpSweep", default=None, metavar="N,N,...",
                   help="QPS scaling sweep over --strategy dp:N replica"
                        " counts, e.g. 1,2,4 (ISSUE 16 perf headline); "
                        "emits one record per point + a summary with "
                        "scaling_vs_linear")
    p.add_argument("--assertScaling", type=float, default=None,
                   metavar="FRAC",
                   help="with --dpSweep: assert aggregate QPS >= FRAC x"
                        " linear at every point (use on real chips; "
                        "CPU replicas share host cores)")
    p.add_argument("--strategy", default=None, metavar="SPEC",
                   help="forwarded to the spawned serve CLI: tp[:K], "
                        "dp[:N], or dp:N+tp:K (ISSUE 16); spawns with "
                        "virtual devices on the CPU platform")
    p.add_argument("--serveArg", action="append", default=[],
                   metavar="ARG",
                   help="extra flag forwarded to the spawned serve CLI "
                        "(repeatable), e.g. --serveArg=--fusedBN "
                        "--serveArg=apply")
    args = p.parse_args(argv)

    if args.chaosSmoke:
        args.endpoint, args.batch = "predict", 2
        return run_chaos_smoke(args)
    if args.fleetSmoke:
        args.endpoint = "generate"
        return run_fleet_smoke(args)
    if args.specSmoke:
        return run_spec_smoke(args)
    if args.quantSmoke:
        return run_quant_smoke(args)
    if args.sloSmoke:
        return run_slo_smoke(args)
    if args.streamSmoke:
        return run_stream_smoke(args)
    if args.tpSmoke:
        return run_tp_smoke(args)
    if args.dpSweep:
        return run_dp_sweep(args)

    proc = None
    if args.url:
        url = args.url.rstrip("/")
    else:
        extra = list(args.serveArg)
        if args.strategy:
            extra += ["--strategy", args.strategy]
        # --smoke also asserts server-vs-client TTFT/TPOT agreement
        # (ISSUE 15 satellite), which needs the lifecycle tracer on the
        # spawned server; an explicit --serveArg=--reqTrace wins
        if args.smoke and "--reqTrace" not in extra:
            extra += ["--reqTrace", "on"]
        proc, url, log_lines = spawn_server(args, extra)
    try:
        if args.smoke:
            run_smoke(url, args)
        else:
            res = closed_loop(url, args)
            prov, page = scrape_provenance(url)
            res["provenance"] = prov
            if args.endpoint == "generate":
                res["spec"] = scrape_spec_columns(page)
                # server-side request-latency columns next to the
                # client-side quantiles (None when --reqTrace off)
                res["server_latency_ms"] = scrape_server_latency(page)
                # quant columns (ISSUE 17): mode + measured guardrail
                # ride every /generate record, "off" included, so A/B
                # lines are self-describing
                res["quant"] = {
                    "quantize": (prov or {}).get("quantize", "off"),
                    "agreement": (prov or {}).get("quant_agreement"),
                    "logit_max_err":
                        (prov or {}).get("quant_logit_max_err"),
                }
            print(json.dumps(res), flush=True)
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                raise SystemExit("server ignored SIGTERM")
            if args.smoke:
                assert rc == 0, f"server exit code {rc} after SIGTERM"
                assert any("serving shutdown clean" in l
                           for l in log_lines), \
                    "missing clean-shutdown marker in server log"
                print("smoke: clean shutdown OK (rc=0)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
