"""Converter/loader at scale (VERDICT r2 missing #4): one scripted
end-to-end — generate a >=100M-edge TEXT edge list, run the C++
lux_converter on it, load the .lux through the native pthread loader,
verify against the in-memory CSC, and (unless -no-run) run the CLI
pagerank on the file.  Every stage timed.

This exercises the exact path the reference tool exists for
(reference tools/converter.cc:85-124: billions of text edges sorted
into binary CSC) at multi-GB size, which the golden tests only cover
on toy files.

Usage: PYTHONPATH=/root/repo python \
    scripts/bench_converter.py [scale ef workdir] [-no-run]
"""

import os
import subprocess
import sys
import time

import numpy as np

scale = int(sys.argv[1]) if len(sys.argv) > 1 else 23
ef = int(sys.argv[2]) if len(sys.argv) > 2 else 16
workdir = sys.argv[3] if len(sys.argv) > 3 else "/tmp/convbench"
no_run = "-no-run" in sys.argv

from lux_tpu import native
from lux_tpu.convert import rmat_edges

os.makedirs(workdir, exist_ok=True)
txt = os.path.join(workdir, f"rmat{scale}_ef{ef}.txt")
lux = os.path.join(workdir, f"rmat{scale}_ef{ef}.lux")

t0 = time.time()
src, dst, nv = rmat_edges(scale=scale, edge_factor=ef, seed=0)
ne = len(src)
print(f"edges generated: nv={nv} ne={ne} ({time.time() - t0:.0f}s)",
      flush=True)

if not os.path.exists(txt):
    import pandas as pd
    t0 = time.time()
    pd.DataFrame({"s": src.astype(np.uint32),
                  "d": dst.astype(np.uint32)}).to_csv(
        txt, sep=" ", header=False, index=False)
    print(f"text edge list written: "
          f"{os.path.getsize(txt) / 1e9:.2f} GB "
          f"({time.time() - t0:.0f}s)", flush=True)

native.ensure_built()
conv = os.path.join(os.path.dirname(native.__file__), "build",
                    "lux_converter")
t0 = time.time()
subprocess.run([conv, "-nv", str(nv), "-ne", str(ne),
                "-input", txt, "-output", lux], check=True)
t_conv = time.time() - t0
print(f"lux_converter: {os.path.getsize(lux) / 1e9:.2f} GB "
      f"({t_conv:.0f}s, {ne / t_conv / 1e6:.1f} M edges/s)", flush=True)

# native loader + structural verification against the in-memory CSC
from lux_tpu.graph import Graph

t0 = time.time()
g = Graph.from_file(lux, use_native=True)
print(f"native load: ({time.time() - t0:.0f}s)", flush=True)
assert g.nv == nv and g.ne == ne
# converter sorts by dst (stable); verify per-vertex edge COUNTS and
# the multiset of sources for a sample of destinations
deg_in = np.bincount(dst, minlength=nv)
np.testing.assert_array_equal(
    np.diff(g.row_ptrs.astype(np.int64), prepend=0), deg_in)
rng = np.random.default_rng(0)
rp = g.row_ptrs.astype(np.int64)
order = np.argsort(dst, kind="stable")     # ONE sort; per-sample
dst_sorted = dst[order]                    # lookups are then O(log ne)
for v in rng.integers(0, nv, 50):
    lo = rp[v - 1] if v else 0
    got = np.sort(g.col_idx[lo:rp[v]])
    a, b = np.searchsorted(dst_sorted, [v, v + 1])
    want = np.sort(src[order[a:b]])
    np.testing.assert_array_equal(got, want)
print("structure verified (degrees exact + 50 sampled vertices)",
      flush=True)

if not no_run:
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "lux_tpu.cli", "pagerank", "-file", lux,
         "-ni", "5"], capture_output=True, text=True)
    print(r.stdout.strip(), flush=True)
    if r.returncode:
        print(r.stderr[-2000:], file=sys.stderr)
        sys.exit(1)
    print(f"cli pagerank end-to-end ({time.time() - t0:.0f}s)",
          flush=True)
