#!/usr/bin/env python3
"""The controls of ``serve_moe_batch``: runs of the cell with one planted
change each, judged by the harness's own comparison (``lib/serve.py
check_and_warm``), and one run that reads the router's flips.
``kda_carry_zeroed`` and ``fp8_reference`` have to come out ``"correct":
false`` everywhere; ``top7`` does in float32 only.

    python3 benchmark/controls/serve_moe_batch.py <control> --seed <n> \
        --seconds 5 --trace 0 [--rehearse-cpu]

``kda_carry_zeroed``  the delta rule forgets: the KDA state is zeroed
                      between the prefill's chunks and before every decode
                      step (what a broken chunk form or a slot handed over
                      without its state does);
``top7``              the router drops each token's eighth expert and
                      renormalises the seven weights left. ``"correct":
                      false`` in float32 (the rehearsal); on the chip it
                      reads inside the cell's own range, because bf16
                      flips the eighth expert of a quarter of the (token,
                      layer) pairs by itself (``flips``; traffic file's
                      ``check_why``);
``fp8_reference``     the nearest precision below the configuration's: the
                      plain reference reads the weights rounded to
                      float8_e4m3, the program the bfloat16 ones;
``flips``             no change to what is compared; ``checks`` gains, on
                      the check's own tokens: how many (token, layer) pairs
                      chose another set of experts in the program's whole
                      bf16 forward than in the reference
                      (``router_flipped_pairs`` of ``router_pairs``), and
                      the logits' error over every position of that
                      forward as it routes itself and with the reference's
                      choices put in place of its own, so with no flip
                      (``logits_rel_err_positions``,
                      ``..._routed_as_reference``);
``none``              no change (the cell itself: ``"correct": true``).

The rest of the command line is ``benchmark/run.py``'s, and so is the
result line. ``tests/benchmark_tests/test_benchmark_solar.py`` runs them
at the rehearsal sizes; PERF.md section 6 has the chip's readings.
"""

import importlib
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "serve_moe_batch"


def kda_carry_zeroed():
    import jax.numpy as jnp

    from bigdl_tpu.nn import linear_attention as la
    chunked, step = la.kda_chunked, la.kda_step

    def chunks_alone(q, k, v, g, beta, s0, chunk=64):
        parts = [chunked(*(t[:, :, i:i + chunk] for t in (q, k, v, g, beta)),
                         jnp.zeros_like(s0), chunk)
                 for i in range(0, q.shape[2], chunk)]
        return jnp.concatenate([o for o, _ in parts], 2), parts[-1][1]

    return mock.patch.multiple(
        la, kda_chunked=chunks_alone,
        kda_step=lambda s, *row: step(jnp.zeros_like(s), *row))


def top7():
    from bigdl_tpu.nn.moe import RoutedFFN
    route = RoutedFFN.route

    def one_fewer(self, params, x):
        idx = route(self, params, x)[0][:, :-1]
        return idx, self.weights(self.scores(params, x), idx)

    return mock.patch.object(RoutedFFN, "route", one_fewer)


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
        want, theirs = reference.routes(params, margs, toks)
        ours = []

        def recording(self, p, x):  # the program's forward, run eagerly
            idx, w = route(self, p, x)
            ours.append(np.asarray(idx))
            return idx, w

        forced = iter(np.asarray(theirs))

        def as_the_reference(self, p, x):  # its choices, our scores
            idx = jnp.asarray(next(forced))
            return idx, self.weights(self.scores(p, x), idx)

        def rel_err(route_fn):
            with mock.patch.object(RoutedFFN, "route", route_fn):
                got = decoder.model.logits(params, np.asarray([toks]))[0]
            return (np.abs(np.asarray(got) - want).max(-1)
                    / np.abs(want).max())

        want = np.asarray(want)
        err, err_forced = rel_err(recording), rel_err(as_the_reference)
        flipped = np.stack([
            (np.sort(a, -1) != np.sort(np.asarray(b), -1)).any(-1)
            for a, b in zip(ours, theirs)])              # (layers, s)
        return dict(
            checks, router_pairs=int(flipped.size),
            router_flipped_pairs=int(flipped.sum()),
            logits_rel_err_positions=float(err.max()),
            logits_rel_err_positions_routed_as_reference=float(
                err_forced.max()))

    return mock.patch.object(serve, "check_and_warm", with_flips)


CONTROLS = {"kda_carry_zeroed": kda_carry_zeroed, "top7": top7,
            "fp8_reference": fp8_reference, "flips": flips,
            "none": mock.MagicMock}


def main(argv):
    if not argv or argv[0] not in CONTROLS:
        print(f"usage: serve_moe_batch.py {'|'.join(CONTROLS)} "
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
