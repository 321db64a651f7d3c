"""On-disk store of the host preparation's products, keyed by content.

A process that prepares a graph some process on this machine has
prepared before LOADS the arrays instead of recomputing them: the
relabelled graph (graph.pair_relabel), the pair plan with its residual
layout (ops/pairs.plan_sharded_pairs) and the push engine's src-sorted
view (ShardedGraph._src_sorted_raw).  It is what the reference's
converter does for the CSC file (sort once, keep the ``.lux``),
extended to the layouts this system derives from it.

An entry is a directory ``<prep_store_dir>/<name>-<key>/`` of plain
``.npy`` files, in the dtypes the products have, beside one
``meta.json``.  It is written under a temporary name and renamed, so a
reader never sees half an entry and two writers of one key are
harmless; an entry that does not load (truncated, another format
version, an array missing) is a miss, recomputed and overwritten.
There is no eviction, no size policy and no index: delete the
directory to clear the store (``runtime.prep_store_dir`` says where it
is).

The key is content, never a path: ``digest`` of the bytes a product is
computed from, ``derive``d onward with every parameter that shapes the
next product, so a run that hits hashes its input graph once.  Whether
the store engages is read from the input: ``engages(ne)`` by size, and
the call sites stay away on multi-host local-parts builds (every
process would have to agree on hit or miss).

The call sites go ``through(name, key, compute, pack, unpack)``.  Each
lookup leaves a ``prep.store`` span (telemetry.span; counts ``hit``,
``miss``, ``bytes``) under the span of the product it serves, each
write a ``prep.store.put`` span (``bytes``).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import uuid

import numpy as np

from lux_tpu import runtime, telemetry

# Part of every key, so entries of another layout are never read.
FORMAT_VERSION = 1

# Graphs with fewer edges than this never touch the store.  Measured
# on this sandbox's CPU and disk (PR 31; R-MAT, edge factor 16, pair
# threshold 16 / min-fill 24, np=1): writing and fsyncing the three
# entries costs 10-20 ms whatever they hold up to 2^16 edges, while
# relabel + plan + src-sort take 20 ms at 2^12 edges, 32 ms at 2^14,
# 47 ms at 2^16, 0.19 s at 2^18, 0.53 s at 2^20 (np=4) and 15.8 s at
# 2^24 (0.13 s to load).  Under about 2^12 edges the store costs more
# than it saves; the constant sits two doublings above that, where a
# miss's write is a third of the preparation it follows.
MIN_EDGES = 1 << 14


def engages(ne: int) -> bool:
    return int(ne) >= MIN_EDGES


def _feed(h, part) -> None:
    if part is None:
        h.update(b"\x00none")
    elif isinstance(part, np.ndarray):
        a = np.ascontiguousarray(part)
        h.update(f"\x00array{a.dtype.str}{a.shape}".encode())
        h.update(memoryview(a).cast("B"))
    else:
        # numbers and strings: NumPy scalars by their Python value
        if isinstance(part, np.generic):
            part = part.item()
        h.update(f"\x00{type(part).__name__}:{part!r}".encode())


def digest(*parts) -> str:
    """Content key of ``parts`` (arrays by dtype, shape and bytes;
    None, numbers and strings by value) under ``FORMAT_VERSION``.
    sha256: 1.37 GB/s here through hashlib, one pass over a graph."""
    h = hashlib.sha256(f"lux-prepstore-v{FORMAT_VERSION}".encode())
    for part in parts:
        _feed(h, part)
    return h.hexdigest()


def derive(key: str, name: str, *params) -> str:
    """Key of the product ``name`` computed from the input under
    ``key`` with ``params``."""
    return digest(key, name, *params)


def through(name: str, key: str | None, compute, pack, unpack):
    """The product ``compute()`` makes, by way of the store: loaded
    (``unpack(arrays, meta)``) where the entry under ``key`` is
    sound, else computed and written (``pack(product)`` -> arrays,
    meta).  ``key`` None: the store stays away."""
    if key is None:
        return compute()
    found = get(name, key)
    if found is not None:
        return unpack(*found)
    product = compute()
    put(name, key, *pack(product))
    return product


def _entry_dir(name: str, key: str) -> str:
    return os.path.join(runtime.prep_store_dir(), f"{name}-{key[:40]}")


def get(name: str, key: str):
    """-> (arrays, meta) of the entry, or None where there is no sound
    one.  ``arrays`` maps each stored name to its array (an array the
    product does not have, such as the weights of an unweighted graph,
    is absent)."""
    with telemetry.span("prep.store") as sp:
        found = _load(_entry_dir(name, key), key)
        if found is None:
            sp.count(hit=0, miss=1, bytes=0)
        else:
            sp.count(hit=1, miss=0,
                     bytes=sum(a.nbytes for a in found[0].values()))
        return found


def _load(d: str, key: str):
    try:
        with open(os.path.join(d, "meta.json")) as f:
            doc = json.load(f)
        if doc["version"] != FORMAT_VERSION or doc["key"] != key:
            return None
        arrays = {}
        for n, (dtype, shape) in doc["arrays"].items():
            a = np.load(os.path.join(d, n + ".npy"), allow_pickle=False)
            if a.dtype.str != dtype or list(a.shape) != shape:
                return None
            arrays[n] = a
        return arrays, doc["meta"]
    except (OSError, ValueError, KeyError, TypeError, EOFError):
        # no entry, a truncated or foreign file: a miss, rebuilt
        return None


def put(name: str, key: str, arrays: dict, meta: dict) -> None:
    """Write the entry; ``arrays`` values that are None are left out.
    A store that cannot be written (full or read-only disk) is no
    error: the product was computed and the next process computes it
    again."""
    arrays = {n: np.asarray(a) for n, a in arrays.items()
              if a is not None}
    final = _entry_dir(name, key)
    tmp = f"{final}.{uuid.uuid4().hex}.partial"
    with telemetry.span("prep.store.put") as sp:
        try:
            os.makedirs(tmp)
            for n, a in arrays.items():
                _write(os.path.join(tmp, n + ".npy"),
                       lambda f, a=a: np.save(f, a, allow_pickle=False))
            doc = {"version": FORMAT_VERSION, "key": key, "name": name,
                   "arrays": {n: [a.dtype.str, list(a.shape)]
                              for n, a in arrays.items()},
                   "meta": meta}
            _write(os.path.join(tmp, "meta.json"),
                   lambda f: f.write(json.dumps(
                       doc, default=_python_value).encode()))
            _publish(tmp, final)
            sp.count(bytes=sum(a.nbytes for a in arrays.values()))
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)


def _python_value(x):
    """json's ``default``: a NumPy scalar in ``meta`` by its value."""
    if isinstance(x, np.generic):
        return x.item()
    raise TypeError(f"{type(x).__name__} is not JSON serializable")


def _write(path: str, fill) -> None:
    # durable before visible: the rename below must not publish a
    # file whose blocks a crash can still lose
    with open(path, "wb") as f:
        fill(f)
        f.flush()
        os.fsync(f.fileno())


def _publish(tmp: str, final: str) -> None:
    """Rename ``tmp`` to ``final``.  What stands there already is an
    entry that did not load or another writer's copy of the same
    content: moved aside first (a directory cannot be renamed over a
    full one), then deleted."""
    for _ in range(2):
        try:
            os.rename(tmp, final)
            return
        except OSError:
            aside = f"{final}.{uuid.uuid4().hex}.stale"
            try:
                os.rename(final, aside)
            except OSError:
                continue        # another writer moved it: try again
            shutil.rmtree(aside, ignore_errors=True)
    shutil.rmtree(tmp, ignore_errors=True)
