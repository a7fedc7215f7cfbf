#!/usr/bin/env python3
"""The controls of ``serve_longmix_batch``: runs of the cell with one
planted change each, judged by the harness's own comparison (``lib/serve.py
check_and_warm``), and one run that reads the router's flips. The four
planted ones have to come out ``"correct": false``; each is planted in the
plain reference, so the program is what the cell times.

    python3 benchmark/controls/serve_longmix_batch.py <control> --seed <n> \
        --seconds 5 --trace 0 [--rehearse-cpu]

``window_off_reference``  the reference's window layers attend the whole
                          prefix: what a ring that never wrapped, or a
                          kernel that ignored its window, computes;
``rope_off_reference``    the reference rotates nothing: what a program
                          that forgot the positions of its window layers
                          (or cached K unrotated into a ring) computes;
``router_after_attention_reference``
                          the reference's router reads RMSNorm2's output,
                          as an ordinary MoE layer's does, not the layer's
                          input;
``fp8_reference``         the nearest precision below the configuration's:
                          the plain reference reads the weights rounded to
                          float8_e4m3, the program the bfloat16 ones;
``flips``                 no change to what is compared; ``checks`` gains,
                          on the check's own tokens: how many (token,
                          layer) pairs chose another six of 64 in the
                          program's whole forward than in the reference
                          (``router_flipped_pairs`` of ``router_pairs``),
                          and the logits' error over the last ``TAIL``
                          positions of that forward as it routes itself
                          and with the reference's choices put in place of
                          its own (``logits_rel_err_tail``,
                          ``..._routed_as_reference``);
``none``                  no change (the cell itself: ``"correct": true``).

The rest of the command line is ``benchmark/run.py``'s, and so is the
result line. ``tests/benchmark_tests/test_benchmark_smallthinker.py`` runs
them at the rehearsal sizes; PERF.md section 6 has the chip's readings.
"""

import importlib
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "serve_longmix_batch"
TAIL = 256  # positions of the whole forward whose logits `flips` compares


def _reference_with(**planted):
    """``serve.load_reference`` handing out the reference with some of
    its functions replaced (before its first call traces them)."""
    from benchmark.lib import serve
    load = serve.load_reference

    def changed(cfg):
        ref = load(cfg)
        for name, fn in planted.items():
            getattr(ref, name)  # a name the reference does not have fails
            setattr(ref, name, fn)
        return ref

    return mock.patch.object(serve, "load_reference", changed)


def window_off_reference():
    return _reference_with(_visible=lambda i, j, window: j <= i)


def rope_off_reference():
    return _reference_with(_rotate=lambda x, theta: x)


def router_after_attention_reference():
    return _reference_with(_router_input=lambda h, m: m)


def fp8_reference():
    return importlib.import_module(
        "benchmark.controls.serve_reason_batch").fp8_reference()


def flips():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import serve, traffic as tg
    from bigdl_tpu.nn.moe import RoutedFFN
    check, route = serve.check_and_warm, RoutedFFN.route

    def with_flips(ctx, decoder, params, reference):
        checks = check(ctx, decoder, params, reference)
        margs, chk = ctx["config"]["model"], ctx["traffic"]["check"]
        toks = tg.token_ids(ctx["seed"], "check", chk["prompt_tokens"],
                            margs["vocab"])
        n, tail = len(toks), min(TAIL, len(toks))
        want, theirs = reference.routes(params, margs, toks)
        want = np.asarray(want)[n - tail:]
        # right-padded to whole blocks (a later token moves no earlier
        # logit), so that on the chip the program's kernels run
        model = decoder.model
        padded = np.zeros((1, -(-n // 512) * 512 if n > 512 else n),
                          np.int32)
        padded[0, :n] = toks
        ours = []

        def recording(self, p, x):  # the program's forward, run eagerly
            idx, w = route(self, p, x)
            ours.append(np.asarray(idx)[:n])
            return idx, w

        forced = iter(np.asarray(theirs))

        def as_the_reference(self, p, x):  # its choices, our scores
            idx = np.asarray(next(forced))
            idx = np.concatenate([idx, np.repeat(
                idx[:1], padded.shape[1] - n, axis=0)])
            idx = jnp.asarray(idx)
            return idx, self.weights(self.scores(p, x), idx)

        def rel_err(route_fn):
            with mock.patch.object(RoutedFFN, "route", route_fn):
                h = model._embed(params, jnp.asarray(padded))
                h, _, _ = model._run(params, h, model.init_cache(
                    1, padded.shape[1], h.dtype))
                got = model._logits(params, h[:, n - tail:n])[0]
            return (np.abs(np.asarray(got, np.float32) - want).max(-1)
                    / np.abs(want).max())

        err, err_forced = rel_err(recording), rel_err(as_the_reference)
        flipped = np.stack([
            (np.sort(a, -1) != np.sort(np.asarray(b), -1)).any(-1)
            for a, b in zip(ours, theirs)])              # (layers, s)
        return dict(
            checks, router_pairs=int(flipped.size),
            router_flipped_pairs=int(flipped.sum()),
            logits_rel_err_tail=float(err.max()),
            logits_rel_err_tail_routed_as_reference=float(
                err_forced.max()))

    return mock.patch.object(serve, "check_and_warm", with_flips)


CONTROLS = {"window_off_reference": window_off_reference,
            "rope_off_reference": rope_off_reference,
            "router_after_attention_reference":
                router_after_attention_reference,
            "fp8_reference": fp8_reference, "flips": flips,
            "none": mock.MagicMock}


def main(argv):
    if not argv or argv[0] not in CONTROLS:
        print(f"usage: serve_longmix_batch.py {'|'.join(CONTROLS)} "
              "[benchmark/run.py's arguments]", file=sys.stderr)
        return 2
    from benchmark import run
    with CONTROLS[argv[0]]():
        return run.main(["--workload", CELL, *argv[1:]])


if __name__ == "__main__":
    rc = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
