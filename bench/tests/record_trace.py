#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out dir>

On one TPU chip: a few calls of the grouped ternary kernel (columns and
rows forms) and of a plain matrix product, under a ``bench.window`` host
span, traced with ``jax.profiler``.  Prints the kernel shapes it ran so
the test can check the reduction against them.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    out = sys.argv[1]
    import jax
    import jax.numpy as jnp

    from repro.kernels.ternary_matmul import ternary_matmul_grouped

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    m, k, n, e = 32, 2048, 1024, 2
    x = jax.random.normal(k0, (m, k), jnp.float32)
    eid = jnp.arange(m, dtype=jnp.int32) % e
    scales = jnp.asarray([0.01, 0.02], jnp.float32)
    bits = jax.random.bits(k1, (e, k, n // 32), jnp.uint32)
    cols = jax.jit(lambda x, p, q: ternary_matmul_grouped(
        x, p, q, scales, eid, interpret=False))
    rows_bits = jax.random.bits(k1, (e, n, k // 32), jnp.uint32)
    rows = jax.jit(lambda x, p, q: ternary_matmul_grouped(
        x, p, q, scales, eid, transpose_rhs=True, interpret=False))
    w = jax.random.normal(k0, (k, n), jnp.bfloat16)
    mm = jax.jit(lambda x, w: x.astype(jnp.bfloat16) @ w)
    jax.block_until_ready((cols(x, bits, bits & 0x0F0F0F0F),
                           rows(x, rows_bits, rows_bits & 0x0F0F0F0F),
                           mm(x, w)))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            jax.block_until_ready(cols(x, bits, bits & 0x0F0F0F0F))
            with jax.profiler.TraceAnnotation("engine.decode_chunk"):
                jax.block_until_ready(rows(x, rows_bits,
                                           rows_bits & 0x0F0F0F0F))
            jax.block_until_ready(mm(x, w))
    jax.profiler.stop_trace()
    print(json.dumps({"calls": 3, "columns": [m, k, n, e],
                      "rows": [m, k, n, e]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
