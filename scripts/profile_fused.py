"""Measure step components correctly: K iterations inside one jit,
tiny output, so output transfer doesn't pollute timings."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.apps import pagerank
from lux_tpu.convert import rmat_edges
from lux_tpu.graph import Graph

SCALE = 21
K = 10

src, dst, nv = rmat_edges(scale=SCALE, edge_factor=16, seed=0)
g = Graph.from_edges(src, dst, nv)
eng = pagerank.build_engine(g, num_parts=1)
sg, lay = eng.sg, eng.tiles
state0 = eng.init_state()
keys = eng._graph_keys
gargs = eng.graph_args
print(f"ne={sg.ne} C={lay.n_chunks} E={lay.E} edges+pad={lay.n_chunks*lay.E}")


def timeit(name, core):
    @jax.jit
    def run(state, *ga):
        def body(i, s):
            return core(s, *ga)
        s = jax.lax.fori_loop(0, K, body, state)
        return jnp.sum(s)

    out = run(state0, *gargs)
    float(out)
    t0 = time.perf_counter()
    out = run(state0, *gargs)
    float(out)
    dt = (time.perf_counter() - t0) / K
    print(f"{name:46s} {dt * 1e3:8.2f} ms/iter "
          f"({sg.ne / dt / 1e9:5.2f} GTEPS)")
    return dt


# full step
timeit("full step", eng._step_core)


# gather-only variant: reduce replaced by cheap sum over E
def core_gather(state, *ga):
    gd = dict(zip(keys, ga))
    flat = state.reshape((sg.num_parts * sg.vpad,) + state.shape[2:])

    def part(old_p, gp):
        sv = jnp.take(flat, gp["src_slot"], axis=0)   # [C, E]
        red = jnp.sum(sv, axis=1)                     # [C]
        # fold [C] back into a state-shaped update so the loop carries
        pad = jnp.zeros(sg.vpad, old_p.dtype).at[:red.shape[0] % sg.vpad
                                                 or sg.vpad].set(0)
        upd = jnp.zeros(sg.vpad, old_p.dtype)
        upd = upd.at[jnp.arange(red.shape[0]) % sg.vpad].add(0)
        return old_p * 0.99 + jnp.sum(red) * 1e-30 + pad + upd * 0

    return jax.vmap(part)(state, gd)


def core_gather_simple(state, *ga):
    gd = dict(zip(keys, ga))
    flat = state.reshape((sg.num_parts * sg.vpad,) + state.shape[2:])

    def part(old_p, gp):
        sv = jnp.take(flat, gp["src_slot"], axis=0)
        return old_p * 0.99 + jnp.sum(sv) * 1e-30

    return jax.vmap(part)(state, gd)


timeit("gather + scalar-sum only", core_gather_simple)


# reduce-only variant: vals = cheap broadcast (no gather)
def core_reduce(state, *ga):
    from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
    from lux_tpu.ops.tiled import combine_chunks
    gd = dict(zip(keys, ga))

    def part(old_p, gp):
        sv = (old_p[:lay.E][None, :] *
              jnp.ones((lay.n_chunks, 1), old_p.dtype))  # [C, E] cheap
        partials = chunk_partials_pallas(sv, lay.W, "sum")
        red = combine_chunks(partials, lay, gp["chunk_start"],
                             gp["last_chunk"], "sum")
        flatshape = (lay.n_tiles * lay.W,)
        out = red.reshape(flatshape)[:sg.vpad]
        return old_p * 0.99 + out * 1e-30

    return jax.vmap(part)(state, gd)


timeit("pallas reduce + combine (no gather)", core_reduce)


# combine-only
def core_combine(state, *ga):
    from lux_tpu.ops.tiled import combine_chunks
    gd = dict(zip(keys, ga))

    def part(old_p, gp):
        partials = (old_p[:lay.W][None, :] *
                    jnp.ones((lay.n_chunks, 1), old_p.dtype))
        red = combine_chunks(partials, lay, gp["chunk_start"],
                             gp["last_chunk"], "sum")
        out = red.reshape((lay.n_tiles * lay.W,))[:sg.vpad]
        return old_p * 0.99 + out * 1e-30

    return jax.vmap(part)(state, gd)


timeit("combine_chunks only", core_combine)
