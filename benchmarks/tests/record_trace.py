#!/usr/bin/env python3
"""How ``recorded_v5e.xplane.pb.gz`` was made (PR 23, one TPU v5e):
one PageRank solve and one BFS at scale 10 under the harness's span
names, python tracer off.

    chiprun -- python3 benchmarks/tests/record_trace.py chiprun_out/rec
"""

import glob
import gzip
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax

    from benchmarks import graphs
    from lux_tpu import runtime
    from lux_tpu.apps import pagerank, sssp
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

    runtime.use_compile_cache()
    paths = graphs.ensure(10, 16, True, 1)
    g = Graph.from_file(paths["lux"], weighted=None)
    g2, _perm, starts = pair_relabel(g, 1, pair_threshold=16)
    sg = ShardedGraph.build(g2, 1, starts=starts, pair_threshold=16)
    pull = pagerank.build_engine(g2, 1, None, sg=sg, pair_threshold=16,
                                 pair_min_fill=24)
    push = sssp.build_engine(g2, start_vertex=0, num_parts=1, sg=sg,
                             pair_threshold=16, pair_min_fill=24)

    def solve():
        with jax.profiler.TraceAnnotation("bench:solve"):
            jax.block_until_ready(pull.run(pull.init_state(), 3))

    def search():
        with jax.profiler.TraceAnnotation("bench:search"):
            label, active = push.init_state()
            jax.block_until_ready(push.converge(label, active))

    solve(), search()                         # compile outside the trace
    tdir = os.path.join(out_dir, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    solve(), search()
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(pb, "rb") as f, gzip.open(os.path.join(
            out_dir, "recorded_v5e.xplane.pb.gz"), "wb") as z:
        z.write(f.read())
    shutil.rmtree(tdir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
