"""Owner-side gather premise test (round 3, VERDICT #1).

The big-table tax: element gathers from tables past ~64-128 MB cost
14.6 ns/elem vs 8.8 below (profile_bigtable.py).  Owner-side message
generation only pays off if a PER-PART gather — each part fetching
from its OWN < 64 MB state shard — actually runs at the small-table
rate.  Three formulations of the same total work (N indices against a
[P, V] state table, every index local to its part):

  flat    one gather from the flattened [P*V] table (today's engine;
          the big-table baseline)
  vmap    jax.vmap over parts of take(state[p], idx[p]) — one batched
          gather; does the emitter see the small per-batch table?
  scan    lax.scan over parts, each step gathering from ONE [V] shard
          (dynamic-slice of the stacked state) — serial over parts,
          but each gather's operand is genuinely small

Methodology: the trusted recipe as a library call
(lux_tpu.timing.loop_bench, the PR-7/round-12 migration off the
documented timing traps): K iterations inside one jit, loop-DEPENDENT
carry, scalar output, host-fetch fence — big operands ride the carry
as jit arguments, and the median over repeats absorbs timing jitter.

Usage: PYTHONPATH=/root/repo \
    python scripts/profile_owner.py [P logV]
"""

import sys
from statistics import median

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.timing import loop_bench

K = 10
P = int(sys.argv[1]) if len(sys.argv) > 1 else 4
logV = int(sys.argv[2]) if len(sys.argv) > 2 else 24   # 64 MB/part f32
V = 1 << logV
N = 1 << 25                      # total indices (33.5M)
Np = N // P
rng = np.random.default_rng(0)

state = jnp.asarray(rng.random((P, V), np.float32))
idx_local = jnp.asarray(rng.integers(0, V, (P, Np)).astype(np.int32))
# the same access pattern as one flat gather from [P*V]
idx_flat = (jnp.arange(P, dtype=jnp.int32)[:, None] * V +
            idx_local).reshape(-1)


def bench(name, fn, idx):
    def step(carry):
        t, i = carry
        sv = fn(t, i)
        return sv, (t + sv * 1e-30, i)

    samples, _ = loop_bench(step, (state, idx), K, repeats=3)
    dt = median(samples)
    print(f"{name:10s} {dt * 1e3:8.2f} ms  ({dt / N * 1e9:6.2f} "
          f"ns/elem)  [{' '.join(f'{s * 1e3:.2f}' for s in samples)}"
          f" ms]", flush=True)


def flat(t, i):
    return jnp.sum(jnp.take(t.reshape(-1), i, axis=0))


def vmapped(t, i):
    return jnp.sum(jax.vmap(lambda tp, ip: jnp.take(tp, ip, axis=0))(
        t, i))


def scanned(t, i):
    def step(acc, x):
        tp, ip = x
        return acc + jnp.sum(jnp.take(tp, ip, axis=0)), None
    out, _ = jax.lax.scan(step, jnp.float32(0), (t, i))
    return out


if __name__ == "__main__":
    print(f"P={P} V={V} ({V * 4 >> 20} MB/part, {P * V * 4 >> 20} MB "
          f"total), N={N}")
    bench("flat", flat, idx_flat)
    bench("vmap", vmapped, idx_local)
    bench("scan", scanned, idx_local)
