#!/usr/bin/env python3
"""Record the small trace that test_trace_reduce.py reads, on a TPU:

    python3 bench/tests/record_trace.py

Three steps, each a call of the program's paged decode kernel and a matmul
inside a ``bench.step`` host span, traced with jax.profiler; the
``.xplane.pb`` goes to ``bench/tests/data/v5e_paged_decode.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.ops import paged_flash_decode  # noqa: E402


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    B, KV, G, hd, P, ps, n = 4, 2, 4, 128, 33, 16, 8
    k = jax.random.normal(jax.random.PRNGKey(0), (KV, P, ps, hd), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, KV, G, hd), jnp.bfloat16)
    tables = (1 + jnp.arange(B * n, dtype=jnp.int32)).reshape(B, n)
    lengths = jnp.array([100, 37, 128, 0], jnp.int32)
    w = jnp.ones((512, 512), jnp.bfloat16)
    step = jax.jit(lambda q, w: (paged_flash_decode(q, k, k, tables, lengths),
                                 w @ w))
    jax.block_until_ready(step(q, w))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready(step(q, w))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "data", "v5e_paged_decode.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(d)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
