"""Collective payload x ndev sweep (round 19, the comm observatory).

Replaces ad-hoc comm timing: every point runs on the trusted
microbenchmark recipe (lux_tpu.timing.loop_bench — loop-DEPENDENT
carry, scalar output, one jit, host-fetch fence), via the library
probe the debts and the comms CLI share (observe.calibrate_links'
``_link_step``).  For each sub-mesh size and payload, one collective
launch per loop step; the wire bytes per step follow the ledger's
ring-algorithm convention (lux_tpu/comms.shipped_bytes), so the
printed GB/s figures are the SAME quantity the per-config comm
ledger prices and ``observe.decompose``'s comm verdict divides by.

On the CPU test mesh the figures are host memcpy rates — recorded,
labeled by the session fingerprint, never fed into scalemodel
(observe.calibrate_links feeds measured rates only on canonical
platforms).  On a multi-chip host this script IS the
ici-bandwidth-probe debt's sweep, one table per mesh size.

Usage: PYTHONPATH=/root/repo \
    python scripts/profile_comm.py [ndevs=2,4,8] [logpayloads=12,16,20]
"""

import sys
from statistics import median

import numpy as np

from lux_tpu import comms
from lux_tpu.observe import _link_step
from lux_tpu.timing import loop_bench

K = 8


def parse_kv(argv):
    out = {}
    for a in argv:
        k, _, v = a.partition("=")
        out[k] = v
    return out


def main(argv=None) -> int:
    import jax

    from lux_tpu.parallel.mesh import make_mesh

    kv = parse_kv(argv if argv is not None else sys.argv[1:])
    avail = len(jax.devices())
    ndevs = [int(x) for x in kv.get("ndevs", "2,4,8").split(",")
             if int(x) <= avail]
    logp = [int(x) for x in kv.get("logpayloads", "12,16,20").split(",")]
    if not ndevs:
        print(f"needs >= 2 devices (have {avail})", file=sys.stderr)
        return 1
    platform = jax.devices()[0].platform
    print(f"platform={platform} devices={avail}  (wire convention: "
          f"lux_tpu/comms.shipped_bytes; K={K} launches/step, "
          f"median of 3)")
    print(f"{'prim':12s} {'ndev':>4s} {'payload/dev':>12s} "
          f"{'s/step':>10s} {'wire B/step':>12s} {'GB/s':>8s}")
    for nd in ndevs:
        mesh = make_mesh(nd)
        tier = comms.mesh_tier(mesh)
        for prim in ("ppermute", "all_to_all"):
            step = _link_step(mesh, prim)
            for lp in logp:
                elems = 1 << lp
                rng = np.random.default_rng(11)
                carry = rng.random(nd * elems, np.float32)
                samples, _ = loop_bench(step, carry, K, repeats=3)
                m = median(samples)
                payload = elems * 4
                wire = comms.shipped_bytes(prim, payload, nd)
                rate = wire / m if m > 0 else 0.0
                print(f"{prim:12s} {nd:>4d} {payload:>10d} B "
                      f"{m:>10.6f} {wire:>12d} "
                      f"{rate / 1e9:>8.3f}  [{tier}]", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
