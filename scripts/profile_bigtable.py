"""Round-3 premise test: the per-edge gather's big-table tax.

Round 1 measured gather flat at 8.97-9.26 ns/elem for 16 KB - 64 MB
tables; scale-25 phases showed ~16.6 ns/edge on a 135 MB table.  This
sweep extends the hoisting-proof harness past 64 MB and adds a
SORTED-index variant (the premise of the two-pass bucketed gather in
PERF_NOTES round-3 pointer #1: if locality matters at big tables,
bucketing by table region pays; if not, it cannot).

Usage: PYTHONPATH=/root/repo \
    python scripts/profile_bigtable.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

K = 10
N = 1 << 25      # 33.5M indices per trial
rng = np.random.default_rng(0)


def bench(name, table, idx):
    def run(t0, i):
        def body(_, c):
            s, t = c
            v = jnp.take(t, i, axis=0)
            sv = jnp.sum(v)
            return (s + sv, t + sv * 1e-30)
        return jax.lax.fori_loop(0, K, body, (jnp.float32(0), t0))[0]

    r = jax.jit(run)
    float(r(table, idx))
    t0 = time.perf_counter()
    float(r(table, idx))
    dt = (time.perf_counter() - t0) / K
    print(f"{name:44s} {dt * 1e3:8.2f} ms  ({dt / N * 1e9:6.2f} "
          f"ns/elem)", flush=True)


for logv in (24, 25, 26):                 # 64 MB, 128 MB, 256 MB f32
    V = 1 << logv
    table = jnp.asarray(rng.random(V, np.float32))
    idx_r = rng.integers(0, V, N).astype(np.int32)
    bench(f"table {V * 4 >> 20:4d} MB, random idx",
          table, jnp.asarray(idx_r))
    bench(f"table {V * 4 >> 20:4d} MB, SORTED idx",
          table, jnp.asarray(np.sort(idx_r)))
    del table
