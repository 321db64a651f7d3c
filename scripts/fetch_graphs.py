#!/usr/bin/env python
"""Downloadable real-graph suite (ROADMAP item 1, round 16).

The reference's own evaluation datasets — Twitter-2010 (LAW/WebGraph)
and the NetFlix prize ratings (reference README.md:88) — are where
page locality actually EXISTS (social/web graphs cluster; R-MAT does
not, the round-15 finding).  This script downloads a chosen dataset,
converts it to the .lux CSC format (lux_tpu/format.py), optionally
runs the page-aware reorder pass and writes its ``.perm`` sidecar,
and fscks the result — so a session with network access can run

    python scripts/fetch_graphs.py twitter-2010 -out /data
    python bench.py -config gather-ab -reorder hillclimb ...

against a real locality-rich graph.  Everything network-facing is
gated and resumable: nothing in tier-1 depends on this script having
run (the offline counterpart is ``convert.community_graph``, the
scrambled planted-partition synthetic).

Sources (mirrors can be swapped with -url):
  twitter-2010  SNAP twitter-2010.txt.gz edge list (~25 GB unpacked;
                41.6M vertices, 1.47B edges)
  netflix       the NetFlix prize rating files are no longer
                hosted first-party; pass -url to a mirror of
                nf_prize_dataset.tar.gz, or use the synthetic
                ``convert.netflix_like_edges`` shape (bench_netflix)

Usage:
    python scripts/fetch_graphs.py DATASET [-out DIR] [-url URL]
        [-reorder none|native|hillclimb] [-np N]
"""

from __future__ import annotations

import argparse
import gzip
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

DATASETS = {
    "twitter-2010": {
        "url": "https://snap.stanford.edu/data/twitter-2010.txt.gz",
        "kind": "edge-list-gz",
    },
    "netflix": {
        "url": None,        # no stable first-party host; pass -url
        "kind": "netflix-tar",
    },
}

def _download(url: str, dest: str) -> str:
    if os.path.exists(dest) and os.path.getsize(dest) > 0:
        print(f"# {dest} already present, skipping download")
        return dest
    print(f"# downloading {url} -> {dest}")
    tmp = dest + ".part"
    with urllib.request.urlopen(url) as r, open(tmp, "wb") as f:
        total = 0
        while True:
            buf = r.read(1 << 22)
            if not buf:
                break
            f.write(buf)
            total += len(buf)
            print(f"\r#   {total / 1e9:.2f} GB", end="",
                  file=sys.stderr)
    print(file=sys.stderr)
    os.replace(tmp, dest)
    return dest


def _chunks(gz_path: str):
    """Yield ~64 MB text chunks split at line boundaries."""
    with gzip.open(gz_path, "rb") as f:
        rem = b""
        while True:
            buf = f.read(1 << 26)
            if not buf:
                if rem.strip():
                    yield rem
                return
            buf = rem + buf
            cut = buf.rfind(b"\n")
            if cut < 0:
                rem = buf
                continue
            yield buf[:cut]
            rem = buf[cut + 1:]


def _parse_pairs(chunk: bytes) -> np.ndarray:
    """Whitespace 'src dst' pairs -> int64 [n, 2] (comment lines
    dropped; no np.loadtxt — its per-line python path is hours over
    a billion-edge file)."""
    if b"#" in chunk:
        chunk = b"\n".join(ln for ln in chunk.split(b"\n")
                           if not ln.lstrip().startswith(b"#"))
    toks = chunk.split()
    if not toks:
        return np.zeros((0, 2), np.int64)
    arr = np.array(toks, dtype=np.int64)
    if arr.size % 2:
        raise ValueError("odd token count — not a 'src dst' list")
    return arr.reshape(-1, 2)


def _edge_list_gz_to_lux(gz_path: str, lux_path: str) -> None:
    """Stream a whitespace 'src dst' edge list (gz) into dst-sorted
    CSC and write .lux, in two passes: a counting pass (ne + max id)
    then a fill pass into PREALLOCATED uint32 arrays — peak memory is
    the 2 x 4 x ne edge arrays plus edges_to_csc's fused-radix
    temporaries (native.sort_kv carries payloads in place), never the
    chunk-list + concatenate doubling a single-pass build would pay
    at the 1.47B-edge Twitter-2010 size."""
    from lux_tpu.convert import edges_to_csc
    from lux_tpu import format as luxfmt

    ne = 0
    vmax = -1
    for chunk in _chunks(gz_path):
        arr = _parse_pairs(chunk)
        if arr.size:
            ne += len(arr)
            vmax = max(vmax, int(arr.max()))
        print(f"\r#   counted {ne / 1e6:.0f} M edges", end="",
              file=sys.stderr)
    print(file=sys.stderr)
    if vmax >= 1 << 32:
        raise ValueError(f"vertex id {vmax} exceeds the .lux uint32 "
                         f"id space")
    src = np.empty(ne, np.uint32)
    dst = np.empty(ne, np.uint32)
    pos = 0
    for chunk in _chunks(gz_path):
        arr = _parse_pairs(chunk)
        if arr.size:
            src[pos:pos + len(arr)] = arr[:, 0]
            dst[pos:pos + len(arr)] = arr[:, 1]
            pos += len(arr)
        print(f"\r#   parsed {pos / 1e6:.0f} M edges", end="",
              file=sys.stderr)
    print(file=sys.stderr)
    assert pos == ne
    nv = vmax + 1
    row_ptrs, col_idx, _w, deg = edges_to_csc(src, dst, nv)
    luxfmt.write_lux(lux_path, row_ptrs, col_idx,
                     degrees=deg.astype(np.uint32))
    print(f"# wrote {lux_path}: nv={nv} ne={len(col_idx)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="download + convert the real-graph suite "
                    "(Twitter-2010 / NetFlix) to .lux")
    ap.add_argument("dataset", choices=sorted(DATASETS))
    ap.add_argument("-out", default=".", help="output directory")
    ap.add_argument("-url", default=None,
                    help="override/mirror URL for the raw download")
    ap.add_argument("-reorder", default="none",
                    choices=["none", "native", "hillclimb"],
                    help="run the page-aware reorder pass "
                         "(lux_tpu/reorder.py) and write the .perm "
                         "sidecar beside the .lux")
    ap.add_argument("-np", type=int, default=1, dest="num_parts",
                    help="partitions the reorder objective scores "
                         "against")
    args = ap.parse_args(argv)

    meta = DATASETS[args.dataset]
    url = args.url or meta["url"]
    if url is None:
        print(f"ERROR: {args.dataset} has no stable first-party "
              f"host; pass -url with a mirror "
              f"(see the module docstring)", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    raw = os.path.join(args.out, os.path.basename(url))
    lux = os.path.join(args.out, args.dataset + ".lux")
    try:
        _download(url, raw)
    except OSError as e:
        print(f"ERROR: download failed ({e}); this script needs "
              f"network access — offline sessions use "
              f"convert.community_graph instead", file=sys.stderr)
        return 1

    if meta["kind"] == "edge-list-gz":
        if not os.path.exists(lux):
            _edge_list_gz_to_lux(raw, lux)
    else:
        print(f"ERROR: no converter implemented for "
              f"{meta['kind']!r} yet; unpack the ratings and use "
              f"scripts/bench_netflix.py's loader", file=sys.stderr)
        return 2

    if args.reorder != "none":
        from lux_tpu import format as luxfmt
        from lux_tpu.graph import Graph
        from lux_tpu.reorder import page_reorder

        g = Graph.from_file(lux, validate=True)
        _g2, perm, rep = page_reorder(g, method=args.reorder,
                                      num_parts=args.num_parts,
                                      verbose=True)
        luxfmt.write_perm_sidecar(lux, perm)
        print(f"# sidecar written: page_fill "
              f"{rep['baseline_fill']} -> {rep['chosen_fill']}")

    import subprocess
    fsck = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fsck_lux.py")
    return subprocess.call([sys.executable, fsck, lux])


if __name__ == "__main__":
    sys.exit(main())
