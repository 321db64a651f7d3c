"""ctypes bindings for the native converter/loader, with auto-build.

The reference's host-side native components are its converter tool and
its per-partition file load tasks (SURVEY.md §2.4); here they are a C++
CLI (converter.cc) and a pthread pread loader (loader.cc).  Python
falls back to the mmap path in lux_tpu.format when the library is not
built or the platform has no toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "build")
_LIB = os.path.join(_BUILD, "liblux_native.so")
CONVERTER = os.path.join(_BUILD, "lux_converter")

_lib = None


def ensure_built(quiet: bool = True) -> bool:
    """Run ``make`` for the native tools and return availability.
    ALWAYS runs it (a no-op when the build is up to date): an existing
    ``build/`` is not trusted, because a tree copied as it stands on
    disk carries the ignored build directory along — what runs must
    be what the sources in THIS tree build."""
    try:
        subprocess.run(["make", "-C", _DIR],
                       check=True,
                       capture_output=quiet)
    except (OSError, subprocess.CalledProcessError):
        return False
    return os.path.exists(_LIB) and os.path.exists(CONVERTER)


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    if not ensure_built():
        raise OSError("native library unavailable: `make -C "
                      f"{_DIR}` failed (no toolchain?)")
    lib = ctypes.CDLL(_LIB)
    _bind(lib)
    _lib = lib
    return lib


def _bind(lib):
    lib.lux_read_header.restype = ctypes.c_int
    lib.lux_read_header.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.lux_load_partition.restype = ctypes.c_int
    lib.lux_load_partition.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
    lib.lux_count_degrees.restype = ctypes.c_int
    lib.lux_count_degrees.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_int]
    lib.lux_rmat_csc.restype = ctypes.c_int
    lib.lux_rmat_csc.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ctypes.c_double, ctypes.c_double, ctypes.c_double,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.lux_argsort_u64.restype = ctypes.c_int
    lib.lux_argsort_u64.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_void_p]
    lib.lux_sort_kv_u64.restype = ctypes.c_int
    lib.lux_sort_kv_u64.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int32)]
    lib.lux_reorder_cluster.restype = ctypes.c_int
    lib.lux_reorder_cluster.argtypes = [
        ctypes.c_uint32, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]


def available() -> bool:
    try:
        _load_lib()
        return True
    except OSError:
        return False


def _check(rc: int, what: str):
    if rc != 0:
        raise OSError(f"{what} failed with native error {rc} "
                      f"({os.strerror(-rc) if rc < 0 else rc})")


def read_header(path: str) -> tuple[int, int]:
    lib = _load_lib()
    nv = ctypes.c_uint32()
    ne = ctypes.c_uint64()
    _check(lib.lux_read_header(path.encode(), ctypes.byref(nv),
                               ctypes.byref(ne)), "read_header")
    return nv.value, ne.value


def load_partition(path: str, nv: int, ne: int, v0: int, v1: int,
                   weighted: bool = False, weight_dtype=np.int32,
                   threads: int = 8):
    """Load vertex range [v0, v1): returns (row_ptrs u64[v1-v0] END
    offsets, col_idx u32[e_hi-e_lo], weights|None, e_lo)."""
    lib = _load_lib()
    e_lo = ctypes.c_uint64()
    e_hi = ctypes.c_uint64()
    # size query
    _check(lib.lux_load_partition(path.encode(), nv, ne, v0, v1,
                                  int(weighted), 4, ctypes.byref(e_lo),
                                  ctypes.byref(e_hi), None, None, None,
                                  threads), "load_partition(size)")
    n_edges = e_hi.value - e_lo.value
    rows = np.empty(v1 - v0, dtype=np.uint64)
    cols = np.empty(n_edges, dtype=np.uint32)
    wdt = np.dtype(weight_dtype)
    weights = np.empty(n_edges, dtype=wdt) if weighted else None
    _check(lib.lux_load_partition(
        path.encode(), nv, ne, v0, v1, int(weighted), wdt.itemsize,
        ctypes.byref(e_lo), ctypes.byref(e_hi),
        rows.ctypes.data_as(ctypes.c_void_p),
        cols.ctypes.data_as(ctypes.c_void_p),
        weights.ctypes.data_as(ctypes.c_void_p) if weighted else None,
        threads), "load_partition")
    return rows, cols, weights, e_lo.value


def count_degrees(path: str, nv: int, ne: int, threads: int = 8):
    lib = _load_lib()
    deg = np.zeros(nv, dtype=np.uint32)
    _check(lib.lux_count_degrees(path.encode(), nv, ne,
                                 deg.ctypes.data_as(ctypes.c_void_p),
                                 threads), "count_degrees")
    return deg


def rmat_csc(scale: int, edge_factor: int = 16, seed: int = 0,
             a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """Generate an R-MAT graph directly as dst-sorted CSC in C++.

    Returns (row_ptrs u64[nv] END offsets, col_idx u32[ne],
    out_degrees u32[nv]).  Same distribution family as
    lux_tpu.convert.rmat_edges but a different RNG stream, so graphs
    are NOT bit-identical to the numpy generator's.
    """
    lib = _load_lib()
    nv = 1 << scale
    ne = nv * edge_factor
    row_ptrs = np.empty(nv, dtype=np.uint64)
    col_idx = np.empty(ne, dtype=np.uint32)
    degrees = np.empty(nv, dtype=np.uint32)
    _check(lib.lux_rmat_csc(
        scale, edge_factor, seed, a, b, c,
        row_ptrs.ctypes.data_as(ctypes.c_void_p),
        col_idx.ctypes.data_as(ctypes.c_void_p),
        degrees.ctypes.data_as(ctypes.c_void_p)), "rmat_csc")
    return row_ptrs, col_idx, degrees


def argsort_u64(keys, threads: int | None = None):
    """Stable parallel radix argsort of non-negative int64/uint64 keys
    (sort.cc).  Single-core hosts run at numpy-radix speed; pod hosts
    scale with cores (PERF_NOTES round-3 #4).  Returns int64 perm."""
    keys = np.ascontiguousarray(keys)
    if keys.dtype == np.int64:
        if keys.size and int(keys.min()) < 0:
            raise ValueError("argsort_u64 needs non-negative keys")
        keys = keys.view(np.uint64)
    elif keys.dtype != np.uint64:
        raise ValueError(f"argsort_u64: unsupported dtype {keys.dtype}")
    if threads is None:
        threads = min(16, os.cpu_count() or 1)
    out = np.empty(keys.size, np.int64)
    lib = _load_lib()
    _check(lib.lux_argsort_u64(
        keys.ctypes.data_as(ctypes.c_void_p), keys.size, int(threads),
        out.ctypes.data_as(ctypes.c_void_p)), "lux_argsort_u64")
    return out


def sort_kv(keys, payloads=(), threads: int | None = None) -> None:
    """Fused stable radix sort IN PLACE: sorts non-negative int64/
    uint64 ``keys`` and carries each array in ``payloads`` (same
    length; element size 1/2/4/8) through the same permutation
    (sort.cc lux_sort_kv_u64).

    This replaces the argsort + one-random-gather-per-array pattern of
    the billion-edge host-prep pipelines (pair_relabel's histogram,
    edges_to_csc, OwnerLayout.build — PERF_NOTES round-4 host prep):
    every radix pass reads sequentially and writes 256 bucketed
    streams, where an argsort pays random key reads per pass and the
    callers then pay one random gather PER payload.  Falls back to
    numpy argsort + in-place takes when the native library is
    unavailable."""
    keys = _as_u64_inplace(keys)
    n = keys.size
    if len(payloads) > 4:            # sort.cc kMaxPay; keep the numpy
        raise ValueError(            # fallback behaviorally identical
            f"sort_kv supports at most 4 payloads, got {len(payloads)}")
    for p in payloads:
        if not isinstance(p, np.ndarray) or not p.flags.c_contiguous:
            raise ValueError("sort_kv payloads must be contiguous "
                             "numpy arrays")
        if p.shape != (n,):
            raise ValueError("sort_kv payloads must match keys' length")
        if p.dtype.itemsize not in (1, 2, 4, 8):
            raise ValueError(f"unsupported payload itemsize "
                             f"{p.dtype.itemsize}")
    if n == 0:
        return
    if not available():
        order = np.argsort(keys, kind="stable")
        keys[:] = keys[order]
        for p in payloads:
            p[:] = p[order]
        return
    if threads is None:
        threads = min(16, os.cpu_count() or 1)
    lib = _load_lib()
    key_tmp = np.empty(n, np.uint64)
    pay_tmp = [np.empty(n, p.dtype) for p in payloads]
    npay = len(payloads)
    PtrArr = ctypes.c_void_p * max(1, npay)
    pays = PtrArr(*[p.ctypes.data for p in payloads])
    tmps = PtrArr(*[p.ctypes.data for p in pay_tmp])
    sizes = (ctypes.c_int32 * max(1, npay))(
        *[p.dtype.itemsize for p in payloads])
    _check(lib.lux_sort_kv_u64(
        keys.ctypes.data_as(ctypes.c_void_p),
        key_tmp.ctypes.data_as(ctypes.c_void_p),
        n, int(threads), npay, pays, tmps, sizes), "lux_sort_kv_u64")


REORDER_MODES = {"cm": 0, "hubs": 1, "communities": 2}


def reorder_cluster(src, dst, nv: int,
                    mode: str | int = "hubs") -> np.ndarray:
    """Clustering vertex reorder (reorder.cc): ``"cm"`` = classic
    ascending-degree Cuthill-McKee BFS, ``"hubs"`` = hub-first BFS
    (descending degree), ``"communities"`` = label-propagation
    community grouping (the Rabbit-order move — BFS leaks across
    clusters; a few LPA rounds recover them) — the page-locality
    preprocessing passes the paged gather needs (ops/pagegather.py;
    sanitize-covered end-to-end: bijection + degree histogram).

    Returns uint32 ``perm`` with ``perm[new] = old`` (the
    degree_relabel direction).  Falls back to a NumPy implementation
    when the native library is unavailable — same contract, slower
    host prep."""
    src = np.ascontiguousarray(src, np.uint32)
    dst = np.ascontiguousarray(dst, np.uint32)
    m = REORDER_MODES.get(mode, mode) if isinstance(mode, str) \
        else int(mode)
    if m not in (0, 1, 2):
        raise ValueError(f"unknown reorder mode {mode!r} (one of "
                         f"{', '.join(REORDER_MODES)})")
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError("reorder_cluster needs matching 1-D src/dst")
    if src.size and (int(src.max()) >= nv or int(dst.max()) >= nv):
        raise ValueError(f"edge endpoint outside [0, {nv})")
    if not available():
        return _reorder_cluster_numpy(src, dst, nv, m)
    perm = np.empty(nv, np.uint32)
    lib = _load_lib()
    _check(lib.lux_reorder_cluster(
        nv, src.size,
        src.ctypes.data_as(ctypes.c_void_p),
        dst.ctypes.data_as(ctypes.c_void_p),
        m,
        perm.ctypes.data_as(ctypes.c_void_p)), "lux_reorder_cluster")
    return perm


def _reorder_cluster_numpy(src, dst, nv: int, mode: int) -> np.ndarray:
    """Pure-NumPy fallback of reorder.cc — identical contract
    (bijection, perm[new] = old), used when the toolchain is missing;
    the C++ path is the production one."""
    from collections import deque

    deg = (np.bincount(src, minlength=nv).astype(np.int64)
           + np.bincount(dst, minlength=nv))
    u = np.concatenate([src, dst]).astype(np.int64)
    v = np.concatenate([dst, src]).astype(np.int64)
    order = np.argsort(u, kind="stable")
    v = v[order]
    off = np.zeros(nv + 1, np.int64)
    np.add.at(off, u + 1, 1)
    off = np.cumsum(off)
    u = u[order]
    if mode == 2:
        # synchronous sort-based label propagation (the C++ pass is
        # async; both converge to community groupings, not to
        # bit-identical orders — the hill-climb scores by measured
        # fill either way)
        labels = np.arange(nv, dtype=np.int64)
        for _ in range(8):
            key = u * np.int64(nv) + labels[v]
            ks = np.sort(key, kind="stable")
            new = np.ones(len(ks), bool)
            new[1:] = ks[1:] != ks[:-1]
            b = np.nonzero(new)[0]
            cnt = np.diff(np.concatenate((b, [len(ks)])))
            uu = ks[b] // nv
            lab = ks[b] % nv
            o2 = np.lexsort((lab, -cnt, uu))
            first = np.ones(len(o2), bool)
            first[1:] = uu[o2][1:] != uu[o2][:-1]
            newlab = labels.copy()
            newlab[uu[o2][first]] = lab[o2][first]
            if np.array_equal(newlab, labels):
                break
            labels = newlab
        # (community by first touch in degree-major order, degree
        # desc, id)
        sweep = np.argsort(-deg, kind="stable")
        rank = np.empty(nv, np.int64)
        rank[sweep] = np.arange(nv)
        comm_rank = np.full(nv, nv, np.int64)
        np.minimum.at(comm_rank, labels, rank)
        return sweep[np.argsort(comm_rank[labels[sweep]],
                                kind="stable")].astype(np.uint32)
    sign = -1 if mode == 1 else 1
    seeds = np.argsort(sign * deg, kind="stable")
    visited = np.zeros(nv, bool)
    out = np.empty(nv, np.uint32)
    pos = 0
    dq = deque()
    for s in seeds:
        if visited[s]:
            continue
        visited[s] = True
        dq.append(int(s))
        while dq:
            x = dq.popleft()
            out[pos] = x
            pos += 1
            nb = v[off[x]:off[x + 1]]
            nb = np.unique(nb[~visited[nb]])
            if nb.size:
                nb = nb[np.argsort(sign * deg[nb], kind="stable")]
                visited[nb] = True
                dq.extend(int(n) for n in nb)
    assert pos == nv
    return out


def _as_u64_inplace(keys):
    """Validate keys for the in-place native sort: contiguous int64
    (non-negative) or uint64; returns a uint64 VIEW of the same
    memory."""
    if not isinstance(keys, np.ndarray) or not keys.flags.c_contiguous:
        raise ValueError("sort_kv keys must be a contiguous numpy array")
    if keys.dtype == np.int64:
        if keys.size and int(keys.min()) < 0:
            raise ValueError("sort_kv needs non-negative keys")
        return keys.view(np.uint64)
    if keys.dtype != np.uint64:
        raise ValueError(f"sort_kv: unsupported key dtype {keys.dtype}")
    return keys


