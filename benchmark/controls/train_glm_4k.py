#!/usr/bin/env python3
"""The controls of ``train_glm_4k``: runs of the cell with one planted
change each, judged by the harness's own comparison
(``lib/train_steps_mtp.py``: the first loss and the timed step's first
gradient against the reference's ``batch_grads``, the parameters' change
over the warm-up steps against a plain AdamW on the reference's
gradients, both heads' logits on one row against the reference's
``routes``), each of which has to come out ``"correct": false``. All but
one are planted in the plain reference, so the program is what the cell
times; the reference's choices of experts come from the planted reference
too.

    python3 benchmark/controls/train_glm_4k.py <control> --seed <n> \
        --seconds 2 --trace 0 [--rehearse-cpu]

``mtp_unshifted_reference``     the reference's MTP module reads the
                                embedding of t_i beside h_i, not of
                                t_{i+1}: a program that forgot the shift;
``latent_norm_off_reference``   the reference drops the RMSNorm of the
                                key/value latent c_kv;
``routed_scale_one_reference``  the reference weights the routed experts
                                by 1.0 in place of routed_scaling_factor
                                1.8;
``fp8_reference``               the nearest precision below the
                                configuration's: the plain reference reads
                                the weights rounded to float8_e4m3, the
                                program the float32 master weights in
                                bfloat16 compute;
``half_batch_reference``        the reference's loss and gradients of a
                                batch read its first half of the rows: a
                                program that trains on half its batch;
``state_unchanged_program``     the program's AdamW computes its moments
                                and leaves the parameters as they were: a
                                step that updates nothing (the gradient
                                and the logits are the cell's);
``none``                        no change (the cell itself: ``"correct":
                                true``).

The rest of the command line is ``benchmark/run.py``'s, and so is the
result line. ``tests/benchmark_tests/test_benchmark_glm.py`` runs them at
the rehearsal sizes; PERF.md section 6 has the chip's readings.
"""

import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "train_glm_4k"


def _reference_with(wrap=None, **planted):
    """The runner's ``load_reference`` handing out the reference with some
    of its functions replaced (before its first call traces them: each
    given the one it replaces), and its weights passed through ``wrap``."""
    from benchmark.lib import train_steps_mtp as runner
    load = runner.load_reference

    def changed(cfg):
        ref = load(cfg)
        for name, make in planted.items():
            setattr(ref, name, make(getattr(ref, name)))
        if wrap is not None:  # heads and mean_nll call routes by name
            routes, grads = ref.routes, ref.batch_grads
            ref.routes = lambda p, *a: routes(wrap(p), *a)
            ref.batch_grads = lambda p, *a: grads(wrap(p), *a)
        return ref

    return mock.patch.object(runner, "load_reference", changed)


def mtp_unshifted_reference():
    return _reference_with(
        _mtp_input_tokens=lambda _: lambda tokens: tokens[:-1])


def latent_norm_off_reference():
    return _reference_with(
        _latent_norm=lambda _: lambda weight, c_kv, eps: c_kv)


def routed_scale_one_reference():
    return _reference_with(_routed_scale=lambda _: lambda model_args: 1.0)


def fp8_reference():
    import jax
    import jax.numpy as jnp

    def round8(tree):
        return jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float8_e4m3fn).astype(v.dtype), tree)

    return _reference_with(wrap=round8)


def half_batch_reference():
    def half(grads):
        return lambda p, m, tokens, targets: grads(
            p, m, tokens[:len(tokens) // 2], targets[:len(targets) // 2])

    return _reference_with(batch_grads=half)


def state_unchanged_program():
    import bigdl_tpu.optim as optim

    class Unmoved(optim.AdamW):
        def update(self, grads, opt_state, params):
            return params, super().update(grads, opt_state, params)[1]

    return mock.patch.object(optim, "AdamW", Unmoved)


CONTROLS = {"mtp_unshifted_reference": mtp_unshifted_reference,
            "latent_norm_off_reference": latent_norm_off_reference,
            "routed_scale_one_reference": routed_scale_one_reference,
            "fp8_reference": fp8_reference,
            "half_batch_reference": half_batch_reference,
            "state_unchanged_program": state_unchanged_program,
            "none": mock.MagicMock}


def main(argv):
    if not argv or argv[0] not in CONTROLS:
        print(f"usage: train_glm_4k.py {'|'.join(CONTROLS)} "
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
