#!/usr/bin/env python3
"""The controls of ``serve_reason_batch``: runs of the cell that have to
come out ``"correct": false`` by the harness's own comparison
(``lib/serve.py check_and_warm``), each by one planted change.

    python3 benchmark/controls/serve_reason_batch.py <control> --seed <n> \
        --seconds 5 --trace 0 [--rehearse-cpu]

``carry_zeroed``   the program's scan forgets: the recurrent state is
                   zeroed between the prefill's chunks and before every
                   decode step (what a broken scan or a slot handed over
                   without its state does);
``fp8_reference``  the nearest precision below the configuration's: the
                   plain reference reads the weights rounded to
                   float8_e4m3, the program the bfloat16 ones;
``none``           no change (the cell itself: ``"correct": true``).

The rest of the command line is ``benchmark/run.py``'s, and so is the
result line. ``tests/benchmark_tests/test_benchmark_sambay.py`` runs all
three at the rehearsal sizes; PERF.md section 6 has the chip's readings.
"""

import os
import sys
import types
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "serve_reason_batch"


def carry_zeroed():
    import jax.numpy as jnp

    from bigdl_tpu.nn import ssm
    scan, step = ssm.selective_scan, ssm._scan_step

    def chunks_alone(x, dt, a, b, c, s0, chunk=64):
        parts = [scan(*(v[:, i:i + chunk] for v in (x, dt)), a,
                      *(v[:, i:i + chunk] for v in (b, c)),
                      jnp.zeros_like(s0), chunk)
                 for i in range(0, x.shape[1], chunk)]
        return jnp.concatenate([y for y, _ in parts], 1), parts[-1][1]

    return mock.patch.multiple(
        ssm, selective_scan=chunks_alone,
        _scan_step=lambda s, *row: step(jnp.zeros_like(s), *row))


def fp8_reference():
    import jax
    import jax.numpy as jnp

    from benchmark.lib import serve
    load = serve.load_reference

    def round8(tree):
        return jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float8_e4m3fn).astype(v.dtype), tree)

    class LayerByLayer(dict):
        """A layer's weights rounded as the reference asks for them: a
        second copy of 7.7 GB does not fit beside the engine."""
        def __getitem__(self, k):
            return round8(dict.__getitem__(self, k))

    def rounded(cfg):
        ref = load(cfg)

        def logits(params, model_args, tokens):
            rest = {k: v for k, v in params.items() if k != "layers"}
            return ref.logits(dict(round8(rest), layers=LayerByLayer(
                params["layers"])), model_args, tokens)

        return types.SimpleNamespace(logits=logits)

    return mock.patch.object(serve, "load_reference", rounded)


CONTROLS = {"carry_zeroed": carry_zeroed, "fp8_reference": fp8_reference,
            "none": mock.MagicMock}


def main(argv):
    if not argv or argv[0] not in CONTROLS:
        print(f"usage: serve_reason_batch.py {'|'.join(CONTROLS)} "
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
