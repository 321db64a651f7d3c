"""Worker for tests/test_worker_kill.py — the kill-one-worker
degraded recovery drill.

Two phases, same file (the reference's "same binary on every node"
model, like tests/mp_worker.py):

- ``distributed``: 2 jax.distributed processes x 4 CPU devices run a
  supervised, checkpointed, HEARTBEAT-SUPERVISED pagerank.  Worker 1
  carries a WORKER_KILL fault plan with ``hard_kill=True`` — at
  segment boundary 1 it os._exit()s with no goodbye, exactly like a
  preempted host.  Worker 0's next heartbeat sync misses the deadline,
  raises the TOPOLOGY-classified WorkerLostError BEFORE entering the
  next segment's collective (no hang), records the agreed shrunken
  topology through the board (propose_shrink), and exits with code 3:
  degraded-relaunch-requested.  jax.distributed cannot drop a member
  in-process, so the shrink is a coordinated RELAUNCH, not an
  in-process mesh rebuild.
- ``solo``: the relaunch.  A single process over its 4 local devices
  resumes from the SHARED checkpoint (written collectively, one
  writer) — the placement metadata records ndev=8, the resuming
  engine has 4, and checkpoint.py routes that into re-placement (a
  ``replace`` event) instead of rejecting it.  The finished state is
  checked against the NumPy oracle.
"""

import os
import sys


def _graph():
    from lux_tpu.convert import uniform_random_edges
    from lux_tpu.graph import Graph

    src, dst = uniform_random_edges(128, 900, seed=5)
    return Graph.from_edges(src, dst, 128)


NI = 10
SEG = 3


def run_distributed(pid: int, nproc: int, port: str, workdir: str):
    from lux_tpu.parallel import multihost
    multihost.initialize(coordinator_address=f"127.0.0.1:{port}",
                         num_processes=nproc, process_id=pid)

    from lux_tpu import faults, heartbeat, resilience
    from lux_tpu.apps import pagerank

    g = _graph()
    mesh = multihost.global_mesh()
    eng = pagerank.build_engine(g, num_parts=8, mesh=mesh)
    hb = heartbeat.Heartbeat(path=os.path.join(workdir, "hb"),
                             pid=pid, nproc=nproc, deadline_s=10.0)
    plan = None
    if pid == 1:
        plan = faults.FaultPlan(schedule={1: faults.WORKER_KILL},
                                hard_kill=True)
    path = os.path.join(workdir, "elastic.ckpt.npz")
    try:
        # guard=False: the finite guard fetches the global state at
        # every boundary; the heartbeat IS the boundary check here
        resilience.supervised_run(
            eng, NI, path, segment=SEG, faults=plan, heartbeat=hb,
            guard=False,
            policy=resilience.RetryPolicy(retries=0, jitter=0,
                                          sleep=lambda s: None))
    except heartbeat.WorkerLostError as e:
        survivors = hb.survivors()
        topo = hb.propose_shrink(survivors, generation=1)
        print(f"SHRINK pid={pid} lost={list(e.lost)} "
              f"survivors={topo['survivors']}", flush=True)
        # os._exit, not sys.exit: interpreter teardown would run
        # jax.distributed's shutdown barrier, which cannot complete
        # with a dead peer and aborts the survivor (exit 1) instead
        # of letting it report the relaunch code.  The checkpoint and
        # the topology record are already durable.
        os._exit(3)
    print(f"MP_ELASTIC_OK pid={pid}", flush=True)


def run_solo(workdir: str):
    import json

    import numpy as np

    from lux_tpu import resilience, telemetry
    from lux_tpu.apps import pagerank
    from lux_tpu.parallel.mesh import make_mesh

    with open(os.path.join(workdir, "hb", "topology.json")) as f:
        topo = json.load(f)
    assert topo["survivors"] == [0], topo

    import jax
    g = _graph()
    ndev = min(4, len(jax.devices()))
    eng = pagerank.build_engine(g, num_parts=8, mesh=make_mesh(ndev))
    path = os.path.join(workdir, "elastic.ckpt.npz")
    ev = telemetry.EventLog(os.path.join(workdir, "solo_events.jsonl"))
    with telemetry.use(events=ev):
        state, report = resilience.supervised_run(
            eng, NI, path, segment=SEG, resume=True,
            policy=resilience.RetryPolicy(retries=0, jitter=0,
                                          sleep=lambda s: None))
    assert ev.counts().get("replace") == 1, ev.counts()
    assert report.initial_resume == SEG, report.initial_resume
    want = pagerank.reference_pagerank(g, NI)
    np.testing.assert_allclose(eng.unpad(state), want, rtol=2e-5)
    print("SOLO_OK", flush=True)


def main():
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    workdir = sys.argv[4]
    phase = sys.argv[5]
    if phase == "solo":
        run_solo(workdir)
    else:
        run_distributed(pid, nproc, port, workdir)


if __name__ == "__main__":
    main()
