"""Flash-attention block-size sweep (chip): the kernel's absolute TF/s
bounds the LM path (PERF.md §8.2 — 16k e2e is attention-bound at the
kernel's ~12 TF/s fwd+bwd vs the chip's ~92 TF/s conv ceiling). Each
(block_q, block_k) changes per-program matmul size and grid overhead;
this times fwd and fwd+bwd per combo and prints one JSON line each.

Usage: python scripts/flash_block_sweep.py [seq] [b] [h] [d]
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def main():
    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    b = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    h = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    d = int(sys.argv[4]) if len(sys.argv) > 4 else 128

    from bigdl_tpu.cli.common import enable_compile_cache
    from bigdl_tpu.ops import flash_attention
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, seq, d), jnp.bfloat16)
    # causal algorithmic flops (live-pair basis, matching the kernels'
    # declared CostEstimate): fwd 2 units, fwd+bwd 6 units over ~s^2/2
    unit = 2.0 * b * h * (seq * seq / 2) * d

    for bq in (128, 256, 512):
        for bk in (128, 256, 512):
            if bq > seq or bk > seq:
                continue
            fn = functools.partial(flash_attention, causal=True,
                                   block_q=bq, block_k=bk)

            def loss(q):
                return jnp.sum(fn(q, q, q).astype(jnp.float32))

            try:
                # Timing rules: (a) chain each call on the previous
                # result so executions cannot be elided/pipelined;
                # (b) sync by FETCHING a value to host — the scalar
                # cannot arrive before the device has produced it
                # (flash_bench's pattern).
                def _sync(x):
                    leaf = jax.tree_util.tree_leaves(x)[0]
                    return float(jnp.sum(leaf.astype(jnp.float32)))

                fwd = jax.jit(fn)
                cur = fwd(q, q, q)
                _sync(cur)
                t0 = time.perf_counter()
                for _ in range(5):
                    cur = fwd(cur, q, q)
                _sync(cur)
                f_ms = (time.perf_counter() - t0) / 5 * 1e3

                g = jax.jit(jax.value_and_grad(loss))
                _, gq = g(q)
                _sync(gq)
                t0 = time.perf_counter()
                for _ in range(5):
                    _, gq = g(gq)
                _sync(gq)
                fb_ms = (time.perf_counter() - t0) / 5 * 1e3
                print(json.dumps({
                    "seq": seq, "bq": bq, "bk": bk,
                    "fwd_ms": round(f_ms, 3),
                    "fwd_tflops": round(2 * unit / f_ms / 1e9, 2),
                    "fwdbwd_ms": round(fb_ms, 3),
                    "fwdbwd_tflops": round(6 * unit / fb_ms / 1e9, 2),
                }), flush=True)
            except Exception as e:  # lowering failure is a result too
                print(json.dumps({"seq": seq, "bq": bq, "bk": bk,
                                  "error": str(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
