"""Find the big-scale cliff: RMAT25/np4 measured 184 ns/edge (no
pair), vs ~18 at scale 23/np1.  Build one graph, time fused runs and
the calibrated phase decomposition across partition counts.

Round 12: the phase split is the observatory's ``decompose``
(lux_tpu/observe.py) — median-of-k + MAD per phase, measured against
the session-scaled scalemodel prediction with drift verdicts, all on
the trusted fence recipe.  The session fingerprint header labels an
off-canon session before any number is read.

Usage: PYTHONPATH=/root/repo \
    python scripts/profile_cliff.py [scale=24] [np list...]
"""

import sys
import time

import numpy as np


def main():
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    nps = [int(x) for x in sys.argv[2:]] or [1, 4]

    from lux_tpu import observe
    from lux_tpu.apps import pagerank
    from lux_tpu.convert import rmat_graph
    from lux_tpu.timing import timed_fused_run

    fp = observe.calibrate()
    t0 = time.time()
    g = rmat_graph(scale=scale, edge_factor=16, seed=0)
    print(f"# graph {time.time() - t0:.0f}s ne={g.ne}", flush=True)

    decomps = []
    for np_parts in nps:
        t0 = time.time()
        eng = pagerank.build_engine(g, num_parts=np_parts,
                                    exchange="gather")
        print(f"# np={np_parts} build {time.time() - t0:.0f}s "
              f"vpad={eng.sg.vpad} epad={eng.sg.epad}", flush=True)
        state, [elapsed] = timed_fused_run(eng, 3)
        assert np.isfinite(eng.unpad(state)).all()
        per_edge = elapsed / 3 / g.ne * 1e9
        print(f"np={np_parts}: {elapsed / 3 * 1e3:.0f} ms/iter  "
              f"{per_edge:.1f} ns/edge  "
              f"({g.ne * 3 / elapsed / 1e9:.4f} GTEPS)", flush=True)
        decomps.append(observe.decompose(
            eng, f"pagerank_np{np_parts}", iters=2, fingerprint=fp))
        del eng, state
    print(observe.render_report(decomps, fp), flush=True)


if __name__ == "__main__":
    main()
