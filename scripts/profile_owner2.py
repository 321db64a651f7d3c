"""Decompose the owner-side step cost on synthetic scale-23-like
geometry: where do the ns/edge go?

Stages (cumulative, all inside one jit, loop-dependent, scalar out):
  gather      scan over P parts: take(state_s, src [C, E])
  +partials   ... + per-chunk compare-reduce (pallas or xla)
  +combine    ... + segmented associative_scan + last-chunk take
  +acc        ... + [P, ntw] accumulate (the full owner contribs)

Usage: PYTHONPATH=/root/repo python \
    scripts/profile_owner2.py [P vpad_m C E method]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

P = int(sys.argv[1]) if len(sys.argv) > 1 else 4
vpad = int(float(sys.argv[2]) * 1e6) if len(sys.argv) > 2 else 4_930_304
C = int(sys.argv[3]) if len(sys.argv) > 3 else 157_000
E = int(sys.argv[4]) if len(sys.argv) > 4 else 256
method = sys.argv[5] if len(sys.argv) > 5 else "pallas"
W = 128
K = 5
vpad = -(-vpad // W) * W
C = -(-C // 8) * 8
n_tiles = vpad // W
G = P * n_tiles
slots = P * C * E

rng = np.random.default_rng(0)
state = jnp.asarray(rng.random((P, vpad), np.float32))
src = jnp.asarray(rng.integers(0, vpad, (P, C, E)).astype(np.int32))
# ~E edges per tile -> chunk tiles mostly distinct, last_chunk ~ identity
rel = jnp.asarray(rng.integers(0, W, (P, C, E)).astype(np.int8))
cs = jnp.asarray(np.ones((P, C), bool))
lc = jnp.asarray(
    np.minimum(np.arange(G) % C, C - 1).astype(np.int32)[None].repeat(
        P, 0))


def bench(name, per_part):
    # big arrays MUST be jit arguments — closed-over constants bloat
    # the program and its compile (CLAUDE.md)
    def run(s0, src_a, rel_a, cs_a, lc_a):
        def body(_, c):
            acc, t = c
            def step(a, x):
                return a + per_part(x[0], x[1], x[2], x[3], x[4]), None
            out, _ = jax.lax.scan(step, jnp.float32(0),
                                  (t, src_a, rel_a, cs_a, lc_a))
            return (acc + out, t + out * 1e-30)
        return jax.lax.fori_loop(0, K, body,
                                 (jnp.float32(0), s0))[0]

    r = jax.jit(run)
    float(r(state, src, rel, cs, lc))
    t0 = time.perf_counter()
    float(r(state, src, rel, cs, lc))
    dt = (time.perf_counter() - t0) / K
    print(f"{name:10s} {dt * 1e3:8.0f} ms  ({dt / slots * 1e9:5.2f} "
          f"ns/slot)", flush=True)


def g_only(st, sr, rl, cs_r, lc_r):
    return jnp.sum(jnp.take(st, sr, axis=0))


def _partials(st, sr, rl):
    from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
    from lux_tpu.ops.tiled import chunk_partials
    vals = jnp.take(st, sr, axis=0)
    if method == "pallas":
        return chunk_partials_pallas(vals, rl, W, "sum")
    vals = jax.lax.optimization_barrier(vals)
    return chunk_partials(vals, rl, W, "sum")


def g_partials(st, sr, rl, cs_r, lc_r):
    return jnp.sum(_partials(st, sr, rl))


class _Lay:
    needs_scan = True


def g_combine(st, sr, rl, cs_r, lc_r):
    from lux_tpu.ops.tiled import combine_chunks
    p = _partials(st, sr, rl)
    tiles = combine_chunks(p, _Lay, cs_r, lc_r, "sum")
    return jnp.sum(tiles)


print(f"P={P} vpad={vpad} C={C} E={E} G={G} slots={slots/1e6:.0f}M "
      f"method={method}")
bench("gather", g_only)
bench("+partials", g_partials)
bench("+combine", g_combine)
