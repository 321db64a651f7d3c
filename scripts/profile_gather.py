"""Isolate TPU gather lowering variants: plain vs vmapped vs one-dim.

Round 12: ported onto the observatory recipe (lux_tpu.timing
.loop_bench — loop-dependent inputs, scalar output, one jit, fetch
fence).  The original hand-rolled timing pattern is exactly the
trap PERF_NOTES documents (XLA hoisting loop-invariant work out of
the timed loop), so these figures supersede it; round
15 grep-gates the pattern out of scripts/ entirely
(scripts/lint_lux.py bench-fence) and adds the paged-vs-flat sweep
below (ops/pagegather.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.observe import median_mad
from lux_tpu.timing import loop_bench

V = 1 << 21
N = 57636 * 1024  # ~59M slots
K = 5

rng = np.random.default_rng(0)
state = jnp.asarray(rng.random(V, np.float32))
idx_flat = jnp.asarray(rng.integers(0, V, N).astype(np.int32))
idx_2d = idx_flat.reshape(-1, 1024)
idx_3d = idx_flat.reshape(-1, 8, 128)


def timeit(name, gather_fn, idx):
    """gather_fn(state, idx) -> gathered values; timed with a
    loop-dependent state carry so the gather cannot hoist."""
    def step(c):
        s, i = c
        sv = jnp.sum(gather_fn(s, i))
        return sv, (s + sv * 1e-30, i)

    samples, _ = loop_bench(step, (state, idx), K, repeats=3)
    dt, mad = median_mad(samples)
    print(f"{name:44s} {dt * 1e3:8.2f} ms  ({N / dt / 1e9:6.2f} G/s, "
          f"mad {mad * 1e3:.2f} ms)")
    return dt


timeit("take flat [N]", lambda s, i: jnp.take(s, i), idx_flat)
timeit("take 2d [C,1024]", lambda s, i: jnp.take(s, i), idx_2d)
timeit("take 3d [C,8,128]", lambda s, i: jnp.take(s, i), idx_3d)

timeit("vmapped take [1,C,1024]",
       jax.vmap(lambda s, i: jnp.take(s, i), in_axes=(None, 0)),
       idx_2d[None])
timeit("vmapped take rows [C rows of 1024]",
       jax.vmap(lambda s, i: jnp.take(s, i), in_axes=(None, 0)),
       idx_2d)

# exact engine formulation: reshape then take
timeit("take axis=0 2d", lambda s, i: jnp.take(s, i, axis=0), idx_2d)

# sum fused over the middle axis
timeit("take+sum fused 3d",
       lambda s, i: jnp.take(s, i.reshape(-1, 8, 128), axis=0)
       .sum(axis=1), idx_flat)


# ---------------------------------------------------------------------
# Paged-vs-flat sweep (round 15, ops/pagegather.py): the same number
# of delivered edges served by (a) the flat per-edge gather and (b)
# the page-binned row fetch + lane shuffle, swept over table size and
# unique-page ratio — the measured side of the scalemodel break-even
# (scalemodel.page_gather_ns).  Both paths include the downstream
# compare-reduce so the A/B isolates the delivery swap.

def paged_sweep(rows=1 << 15, loop_k=4):
    from lux_tpu.ops.pagegather import lane_resolve
    from lux_tpu.ops.tiled import chunk_partials
    from lux_tpu import scalemodel

    method = "pallas" if jax.default_backend() == "tpu" else "xla"
    edges = rows * 128
    print(f"\n# paged-vs-flat sweep: {rows} rows x 128 lanes "
          f"({edges / 1e6:.1f}M edges), lane resolve = {method}")
    for logv in (18, 21, 24):
        T = (1 << logv) // 128
        tbl = jnp.asarray(rng.random((T, 128), np.float32))
        flat_tbl = tbl.reshape(-1)
        for pages_frac in (0.02, 0.25, 1.0):
            n_pages = max(1, int(T * pages_frac))
            slot = rng.integers(0, n_pages, rows)
            page_ids = jnp.asarray(
                rng.choice(T, size=n_pages, replace=False)
                .astype(np.int32))
            lane = rng.integers(0, 128, (rows, 128))
            sl = jnp.asarray(
                (slot[:, None].astype(np.uint32) << np.uint32(7))
                | lane.astype(np.uint32))
            rel = jnp.asarray(
                rng.integers(0, 128, (rows, 128)).astype(np.int8))
            flat_idx = jnp.asarray(
                rng.integers(0, T * 128,
                             (rows, 128)).astype(np.int32))

            def flat_step(c):
                t, i, r = c
                v = jax.lax.optimization_barrier(
                    jnp.take(t, i, axis=0))
                sv = jnp.sum(chunk_partials(v, r, 128, "sum"))
                return sv, (t + sv * 1e-30, i, r)

            def paged_step(c):
                t, ids, s, r = c
                pages = jnp.take(t, ids, axis=0)
                rs = jax.lax.shift_right_logical(
                    s[:, 0], jnp.uint32(7)).astype(jnp.int32)
                rws = jnp.take(pages, rs, axis=0)
                v = jax.lax.optimization_barrier(
                    lane_resolve(rws, s, method))
                sv = jnp.sum(chunk_partials(v, r, 128, "sum"))
                return sv, (t + sv * 1e-30, ids, s, r)

            fs, _ = loop_bench(flat_step, (flat_tbl, flat_idx, rel),
                               loop_k, repeats=3)
            ps, _ = loop_bench(paged_step, (tbl, page_ids, sl, rel),
                               loop_k, repeats=3)
            fm, _ = median_mad(fs)
            pm, _ = median_mad(ps)
            ratio = n_pages * 128 / edges
            model = scalemodel.page_gather_ns(ratio, 128.0)
            print(f"table 2^{logv}  page_ratio {ratio:7.4f}  "
                  f"flat {fm / edges * 1e9:6.2f} ns/e  "
                  f"paged {pm / edges * 1e9:6.2f} ns/e  "
                  f"(model {model:5.2f})  "
                  f"speedup {fm / pm:5.2f}x")


paged_sweep()
