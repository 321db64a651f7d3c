"""Separate compute speed from data-movement speed on the TPU.

Round 15: ported onto the observatory recipe (lux_tpu.timing
.loop_bench — loop-dependent carry, scalar output, one jit, fetch
fence); the old block_until_ready pattern is the PERF_NOTES trap and
is now grep-gated out of scripts/ (lint_lux bench-fence).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.observe import median_mad
from lux_tpu.timing import loop_bench

REPS = 5
rng = np.random.default_rng(0)


def timeit(name, fn, x0, work=0, bytes_=0):
    """fn(x) -> array; the x carry is loop-dependent so XLA cannot
    hoist the op out of the timed loop."""
    def step(c):
        (x,) = c
        out = fn(x)
        sv = jnp.sum(out.ravel()[:1]).astype(jnp.float32)
        return sv, (x + (sv * 1e-30).astype(x.dtype),)

    samples, _ = loop_bench(step, (x0,), REPS, repeats=3)
    dt, _mad = median_mad(samples)
    extra = []
    if work:
        extra.append(f"{work / dt / 1e12:7.2f} TFLOP/s")
    if bytes_:
        extra.append(f"{bytes_ / dt / 1e9:7.2f} GB/s")
    print(f"{name:40s} {dt * 1e3:9.2f} ms  " + "  ".join(extra))
    return dt


# big matmul: compute-bound
for n in (4096, 8192):
    a = jnp.asarray(rng.random((n, n), np.float32), dtype=jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    timeit(f"matmul {n} bf16", f, a, work=2 * n**3)

# elementwise on big array: HBM-bound
x = jnp.asarray(rng.random((4096, 4096), np.float32))
f = jax.jit(lambda x: x * 1.0001 + 0.5)
timeit("elementwise 16M f32 (xla)", f, x, bytes_=2 * x.nbytes)

x2 = jnp.asarray(rng.random((16384, 4096), np.float32))
timeit("elementwise 64M f32 (xla)", f, x2, bytes_=2 * x2.nbytes)

# reduction
f = jax.jit(lambda x: jnp.sum(x))
timeit("sum 64M f32 (xla)", f, x2, bytes_=x2.nbytes)

# many small iterations inside one jit: dispatch/compute latency
y = jnp.asarray(rng.random((8, 128), np.float32))


@jax.jit
def loop_small(y):
    def body(i, y):
        return y * 1.0001 + 1e-6

    return jax.lax.fori_loop(0, 10000, body, y)


timeit("10k tiny fori iterations (one jit)", loop_small, y)
