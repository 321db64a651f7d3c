#!/usr/bin/env python3
"""Probe: what a queue slot's search costs on the chip.

``jnp.searchsorted(method="scan")``, the binary search the queue stage
ran until PR 48, against the row search (``engine.frontier``'s, and
its unrolled form at several fan-outs), on the two kinds of table the
stage searches: the running count of a 0.15%-dense mask
(``pick_queue``: plateaus, queries 1..Q) and a sorted id table
(``frontier_extents``: distinct ids, a sorted sample of them as
queries).  One chip call, no cell's code (it refuses any device but
a TPU: its numbers set ``frontier.ROW_FANOUT``):

    chiprun --timeout 1500 -- python3 scripts/probe_row_search.py

Every program is a loop of ``--reps`` searches inside one jit
(``lux_tpu.timing.loop_bench``), each fed by the one before so that
none is hoisted or dropped.  Two programs a point: ``descend``
searches a table whose tree is built outside the loop (a graph's
``src_ids``), ``total`` rebuilds the table (one elementwise pass, the
binary search's too) and the tree every time (a trip's ``ranks``).
Printed: ns a query of both.  What a form costs in compiled code,
which is device memory, needs no chip: compile it for one (PERF.md,
PR 48).  Lines go to ``chiprun_out/probe_row_search.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
from jax import lax                                     # noqa: E402

from lux_tpu.engine import frontier as fr               # noqa: E402
from lux_tpu.timing import loop_bench                   # noqa: E402

TABLES = (1_200_000, 1_890_816, 8_400_000)
QUEUES = (14_784, 118_276, 524_688)
FANOUTS = (4, 8, 16, 32, 128)
MASK_DENSITY = 0.0015
CHUNKS = (1024, 4096, 16384)
HEAD = 512      # splitters the unrolled forms compare at once


def _binary(table, queries):
    return jnp.searchsorted(table, queries, side="left",
                            method=fr.SEARCH).astype(jnp.int32)


def _levels(table, fanout, leaf=None):
    """The UNROLLED form's tree: (head, levels top-down), every level
    its own [n, F] array (rows of ``leaf`` at the bottom), the head
    the at most ``HEAD`` splitters compared without a fetch."""
    big = jnp.iinfo(table.dtype).max
    levels, flat, F = [], table, leaf or fanout
    while True:
        n = -(-flat.shape[0] // F)
        if n * F != flat.shape[0]:
            flat = jnp.concatenate([flat, jnp.full(
                (n * F - flat.shape[0],), big, flat.dtype)])
        levels.append(flat.reshape(n, F))
        flat, F = levels[-1][:, -1], fanout
        if n <= HEAD:
            return flat, tuple(reversed(levels))


def _descend(head, levels, queries, length, fetch):
    q = queries.astype(head.dtype)[:, None]
    node = jnp.sum(head[None, :] < q, axis=1, dtype=jnp.int32)
    for rows in levels:
        node = jnp.minimum(node, rows.shape[0] - 1)
        node = node * rows.shape[1] + jnp.sum(
            fetch(rows, node) < q, axis=1, dtype=jnp.int32)
    return jnp.minimum(node, length)


def _fetch_rows(rows, node):
    return rows.at[node].get(mode="promise_in_bounds")


def _fetch_window(rows, node):
    """A row as a window of F elements gathered out of the FLAT level,
    so that no level is laid out as [n, F] (which pads 16 lanes to 128
    in device memory)."""
    dn = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    F = rows.shape[1]
    return lax.gather(rows.reshape(-1), (node * F)[:, None], dn, (F,),
                      mode="promise_in_bounds")


def _stack_head_search(rows, length, queries):
    """``fr.table_search`` with the one-row top level compared against
    every query at once in place of Q fetches of the same row."""
    F = rows.shape[-1]
    plan = fr.row_plan(length)
    q = queries.astype(rows.dtype)[:, None]
    node = jnp.sum(rows[plan[0][0]][None, :] < q, axis=1,
                   dtype=jnp.int32)
    for first, count in plan[1:]:
        node = jnp.minimum(node, count - 1)
        row = rows.at[first + node].get(mode="promise_in_bounds")
        node = node * F + jnp.sum(row < q, axis=1, dtype=jnp.int32)
    return jnp.minimum(node, length)


def _pieces_search(rows, length, queries, chunk):
    """``fr.table_search`` over pieces of ``chunk`` queries (the last
    one filled up) by one rolled loop: less compiled code a search,
    more time (PERF.md, PR 48: tried and dropped)."""
    Q = queries.shape[0]
    if Q <= chunk:
        return fr.table_search(rows, length, queries)
    n = -(-Q // chunk)
    pieces = jnp.concatenate(
        [queries, jnp.zeros((n * chunk - Q,), queries.dtype)])
    return lax.map(lambda piece: fr.table_search(rows, length, piece),
                   pieces.reshape(n, chunk)).reshape(-1)[:Q]


def methods():
    """name -> (build(table) -> tree, search(tree, queries, N)).
    ``binary`` is what the queue stage ran, ``stack128`` what it runs
    (``fr.row_table`` / ``fr.table_search``: all levels stacked in one
    array, the descent one rolled loop; ``.cC``: over pieces of C
    queries; ``.head``: unrolled, the top row compared and not
    fetched); ``rowsF`` is the unrolled descent over levels of their
    own, at every fan-out."""
    def unrolled(fetch, **kw):
        return (functools.partial(_levels, **kw),
                lambda tree, q, n: _descend(*tree, q, n, fetch))

    out = {"binary": (lambda t: t, lambda t, q, n: _binary(t, q)),
           "stack128": (fr.row_table, lambda rows, q, n:
                        fr.table_search(rows, n, q)),
           "stack128.head": (fr.row_table, lambda rows, q, n:
                             _stack_head_search(rows, n, q))}
    for chunk in CHUNKS:
        out[f"stack128.c{chunk}"] = (
            fr.row_table, lambda rows, q, n, chunk=chunk:
            _pieces_search(rows, n, q, chunk))
    for F in FANOUTS:
        out[f"rows{F}"] = unrolled(_fetch_rows, fanout=F)
    out["rows16.leaf128"] = unrolled(_fetch_rows, fanout=16, leaf=128)
    out["window16"] = unrolled(_fetch_window, fanout=16)
    return out


@functools.lru_cache(maxsize=1)
def make_inputs(kind: str, N: int, Q: int, seed: int):
    """(table, queries, the positions ``np.searchsorted`` gives)."""
    rng = np.random.default_rng(seed)
    if kind == "ranks":
        table = np.cumsum(rng.random(N) < MASK_DENSITY, dtype=np.int64)
        queries = np.arange(1, Q + 1)
    else:
        table = np.sort(rng.choice(4 * N, size=N, replace=False))
        hits = np.sort(rng.choice(N, size=min(Q, N), replace=False))
        queries = np.resize(table[hits], Q)
        queries[::7] += 1           # some ids the table lacks
    table, queries = table.astype(np.int32), queries.astype(np.int32)
    return table, queries, np.searchsorted(table, queries, side="left")


def probe_point(name, build, search, kind, N, Q, reps, parts, seed):
    """One line of the table: ns a query of ``descend`` (the tree
    rides the loop's carry untouched: built once) and of ``total``
    (the table is rebuilt from the carry every trip, and its tree),
    each the least of three timed calls of ``lux_tpu.timing
    .loop_bench``'s one jitted loop of ``reps`` searches.  The loop's
    scalar counts the positions that differ from NumPy's: 0, or the
    probe stops."""
    table, queries, want = make_inputs(kind, N, Q, seed)

    def find(tree, q):
        return search(tree, q, N)

    if parts:
        # the engine's shape on a mesh: [1, N] tables under the
        # per-part vmap, the gathered queue shared
        table, want = table[None], want[None]
        build, find = jax.vmap(build), jax.vmap(find, in_axes=(0, None))
    table, queries, want = map(jnp.asarray, (table, queries, want))

    def stepper(make_tree):
        def step(carry):
            x, queries, want, nudge = carry
            pos = find(make_tree(x, nudge), queries + nudge)
            # always 0, and only the loop's last search knows it
            return (jnp.sum(pos != want).astype(jnp.float32),
                    (x, queries, want, pos.reshape(-1)[0] >> 30))
        return step

    line = dict(method=name, kind=kind, table=N, queries=Q,
                parts=int(parts), reps=reps)
    for key, step, x in (
            ("descend", stepper(lambda tree, nudge: tree),
             jax.jit(build)(table)),
            ("total", stepper(lambda t, nudge: build(t + nudge)), table)):
        seconds, wrong = loop_bench(
            step, (x, queries, want, jnp.int32(0)), reps)
        if wrong:
            raise SystemExit(f"{name} {kind} {N} {Q}: {wrong:.0f} "
                             f"wrong positions")
        line[f"{key}_ns_per_query"] = min(seconds) * 1e9 / Q
    return line


def grid(tables, queues):
    """(method, kind, N, Q, parts): the binary search and the stacked
    forms alone at every size, and under the one-part vmap at the
    middle table; the unrolled forms (for the record of what a row of
    F lanes costs) at the least queue on every table and at every
    queue on the middle one, alone."""
    mid = tables[len(tables) // 2]
    for N in tables:
        for Q in queues:
            for kind in ("ranks", "ids"):
                for name in methods():
                    kept = name == "binary" or name.startswith("stack")
                    if kept or N == mid or Q == queues[0]:
                        yield name, kind, N, Q, False
                    if kept and N == mid:
                        yield name, kind, N, Q, True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tables", type=int, nargs="+", default=TABLES)
    ap.add_argument("--queues", type=int, nargs="+", default=QUEUES)
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--out", default="chiprun_out/probe_row_search.jsonl")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        print("probe_row_search: a TPU's numbers only; this is "
              f"{dev.platform}", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    table = methods()
    with open(args.out, "w") as out:
        for name, kind, N, Q, parts in grid(args.tables, args.queues):
            line = probe_point(name, *table[name], kind, N, Q,
                               args.reps, parts, args.seed)
            line["device"] = dev.device_kind
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(f"{name:15s} {kind:5s} N={N:>9,} Q={Q:>7,} "
                  f"parts={int(parts)} "
                  f"descend {line['descend_ns_per_query']:8.2f} "
                  f"total {line['total_ns_per_query']:8.2f} ns/query",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
