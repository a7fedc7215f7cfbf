"""Traffic kind ``closed_loop_agreed_rows``: ``lib/serve.py``'s closed loop,
letter for letter, with a check that reads the logits at the rows the
program routed as the reference did.

``serve.check_and_warm`` compares every row it reads. With many routed
experts under random N(0, 0.02) weights the router's scores lie a few
hundredths apart, bfloat16 rounding turns some token's choice, and a row
whose held experts flipped moves by as much as a planted fault does. So,
as ``lib/train_steps_mtp.py`` does for training, the rows are told apart
by the routing that both sides report:

* the program's rows: the check prompt's last ``tail_rows`` positions
  through the model's own prefill path (``_run`` over the prompt's bucket
  with a slot's cache, ``last`` at the prompt's end, the same layers the
  engine's prefill program runs), with their held experts as bit sets; the
  engine's prefill row and its ``decode_steps`` decode rows through the
  slot's rings and cache, the decode rows' bit sets those the engine's
  step returns beside its tokens (read where the engine hands them to
  ``model.count_step``), the prefill row's those of the prefill path at
  the same position;
* the reference's ``routes`` of the same tokens: logits, and the experts
  each row chose in each layer;
* ``logits_rel_err``: the largest error over the reference's largest
  |logit| at the engine's rows (the prefill row and the decode rows,
  ``serve.check_and_warm``'s) whose held experts agree with the
  reference's in every layer; ``flipped_share``: the share of all rows,
  the prompt's tail among them, where they do not. ``correct`` holds the
  first under ``rel_tol`` and the second under ``flip_share_tol``. Beside
  them ``logits_rel_err_tail``, the same largest error over the tail's
  agreeing rows, and ``logits_rel_err_all_rows``: a tail row whose own
  routing agrees still attends to earlier rows whose routing flipped,
  and on the chip a few such rows read several times the engine rows'
  error, so neither decides anything.

``compare`` and ``judged`` are the comparison; the controls of a cell of
this kind (``benchmark/controls/<cell>.py``) put a planted reference
through them against the same program rows."""

import time
from unittest import mock

import numpy as np

from . import serve, traffic as tg


def held_sets(chosen, held, share):
    """(rows, held) bool: the held experts each row chose, of the global
    expert ids ``chosen`` (rows, top_k)."""
    local = np.asarray(chosen) - share * held
    return np.stack([(local == e).any(-1) for e in range(held)], -1)


def bit_sets(picked, held):
    """(rows, layers, held) bool of the program's bit sets (rows, layers,
    words)."""
    e = np.arange(held)
    bits = np.asarray(picked, np.uint32)
    return ((bits[..., e // 32] >> (e % 32).astype(np.uint32)) & 1) > 0


def program_rows(ctx, decoder, params):
    """The check's tokens and the program's rows: ``{"tokens",
    "positions", "logits", "held", "engine"}``, rows in the order of
    ``positions`` (the tail of the prompt, then the engine's prefill row
    and decode rows), ``held`` (rows, layers, held) bool, ``engine`` the
    number of the engine's rows at the end."""
    import jax
    import jax.numpy as jnp

    margs, chk = ctx["config"]["model"], ctx["traffic"]["check"]
    model, n = decoder.model, chk["decode_steps"]
    toks = tg.token_ids(ctx["seed"], "check", chk["prompt_tokens"],
                        margs["vocab"])
    seen = []
    count = model.count_step

    def recording(picked):  # the engine's step's own bit sets
        seen.append(np.asarray(picked))
        return count(picked)

    with mock.patch.object(model, "count_step", recording):
        fut = decoder.submit(toks, n + 1)
        slot = next(i for i, r in enumerate(decoder._reqs) if r is not None)
        got = [np.asarray(decoder._logits)[slot]]
        for _ in range(n):
            decoder.step()
            got.append(np.asarray(decoder._logits)[slot])
        decoder.step()
    out = fut.result(0)

    size, tail = len(toks), min(chk["tail_rows"], len(toks))
    bucket = decoder.prompt_bucket_for(size)

    @jax.jit
    def prefill_rows(p, tokens):
        h = model._embed(p, tokens)
        h, _, picked = model._run(p, h, model.init_cache(
            1, decoder.max_len, decoder.cache_dtype), last=size - 1)
        return (model._logits(p, h[:, size - tail:size]).astype(
            jnp.float32)[0], picked[0, size - tail:size])

    padded = np.zeros((1, bucket), np.int32)
    padded[0, :size] = toks
    tail_logits, tail_picked = jax.device_get(prefill_rows(
        params, jnp.asarray(padded)))
    # the engine's prefill row routes as the prefill path's last row
    picked = np.concatenate([tail_picked, tail_picked[-1:], *seen[:n]])
    return {"tokens": toks + out[:n], "out": out,
            "positions": list(range(size - tail, size))
            + list(range(size - 1, size + n)),
            "logits": np.concatenate([tail_logits, np.stack(got)]),
            "held": bit_sets(picked, model.experts_held), "engine": n + 1}


def compare(rows, reference, params, margs):
    """The checks of the program's ``rows`` against ``reference``."""
    want, chosen = reference.routes(params, margs, rows["tokens"])
    pos = np.asarray(rows["positions"])
    want = np.asarray(want)[pos]
    theirs = np.stack([held_sets(np.asarray(c)[pos], margs["experts_held"],
                                 margs.get("share", 0)) for c in chosen],
                      axis=1)                      # (rows, layers, held)
    agree = np.all(rows["held"] == theirs, axis=(1, 2))
    scale = float(np.abs(want).max())
    errs = np.abs(rows["logits"] - want).max(-1) / scale
    worst = lambda keep: float(errs[keep].max()) if keep.any() \
        else float("inf")
    engine = np.arange(agree.size) >= agree.size - rows["engine"]
    return {"logits_rel_err": worst(agree & engine),
            "flipped_share": float(1 - agree.mean()),
            "logits_rel_err_tail": worst(agree & ~engine),
            "logits_rel_err_all_rows": float(errs.max()),
            "logits_scale": scale, "rows": int(agree.size),
            "greedy_tokens_in_vocab": all(0 <= v < margs["vocab"]
                                          for v in rows["out"])}


def judged(checks, chk):
    """The comparison's verdict on ``checks`` (``correct`` less the
    window's responses)."""
    return bool(checks["logits_rel_err"] < chk["rel_tol"]
                and checks["flipped_share"] < chk["flip_share_tol"]
                and checks["greedy_tokens_in_vocab"])


def warm_buckets(ctx, decoder):
    """One request through every prefill bucket the mix's prompt lengths
    fall into but the check's (``serve.check_and_warm``'s second half)."""
    parts, seed = ctx["parts"], ctx["seed"]
    margs, chk = ctx["config"]["model"], ctx["traffic"]["check"]
    t = time.perf_counter()
    buckets = {}
    for p, _ in tg.length_pairs(ctx["traffic"]):
        b = decoder.prompt_bucket_for(p)
        buckets[b] = max(p, buckets.get(b, 0))
    for b, p in sorted(buckets.items()):
        if b != decoder.prompt_bucket_for(chk["prompt_tokens"]):
            t_b = time.perf_counter()
            decoder.generate(tg.token_ids(seed, f"warm{b}", p,
                                          margs["vocab"]), 2)
            parts[f"prefill_bucket_{b}_s"] = time.perf_counter() - t_b
    parts["warm_buckets_s"] = time.perf_counter() - t
    return sorted(buckets)


def check_and_warm(ctx, decoder, params, reference):
    """``serve.check_and_warm``'s place: the program's rows against the
    reference, then the buckets warmed."""
    t = time.perf_counter()
    rows = program_rows(ctx, decoder, params)
    checks = compare(rows, reference, params, ctx["config"]["model"])
    ctx["parts"]["reference_and_first_programs_s"] = time.perf_counter() - t
    checks["prefill_buckets"] = warm_buckets(ctx, decoder)
    return checks


def run(ctx):
    ctx = dict(ctx, traffic=dict(ctx["traffic"], kind="closed_loop"))
    with mock.patch.object(serve, "check_and_warm", check_and_warm):
        out = serve.run(ctx)
    checks = out["checks"]
    out["correct"] = bool(judged(checks, ctx["traffic"]["check"])
                          and checks["responses_exact"])
    return out
