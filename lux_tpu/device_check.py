"""On-device sharded correctness audits (the scalable ``-check``).

The reference's ``-check`` audits run as GPU tasks per partition over
the resident edge arrays, at full graph scale (reference
sssp_gpu.cu:800-843, components_gpu.cu:788, with per-part [PASS]/[FAIL]
prints at sssp_gpu.cu:837-842).  The host audits in ``lux_tpu.check``
re-materialize the whole edge list in NumPy — fine at test scale,
impossible for a sharded billion-edge run on a pod.

Here the same audits are per-part jitted reductions over the
ShardedGraph's part-major edge arrays, sharded over the ``parts`` mesh
axis exactly like the engines (shard_map + all_gather of the audited
state).  The NumPy versions in ``check.py`` remain the oracles
(tests/test_check_device.py verifies count-exact agreement).

Notes:
- Graph arrays are jit ARGUMENTS (never closed over) per the repo
  convention.
- The pagerank residual audit re-derives one pull iteration with the
  portable scatter-based segment reduce — slower than the engines'
  tiled path but a one-off audit, not the hot loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from lux_tpu.check import CheckResult
from lux_tpu.graph import ShardedGraph
from lux_tpu.ops.segment import segment_reduce
from lux_tpu.parallel.mesh import PARTS_AXIS, shard_over_parts


def _as_padded(sg: ShardedGraph, state):
    """Accept either the engine's padded [rows, vpad, ...] state
    (device or host; on multi-host runs the GLOBAL [num_parts, ...]
    array) or a host user-order [nv, ...] array."""
    if (getattr(state, "ndim", 0) >= 2 and state.shape[1] == sg.vpad
            and state.shape[0] in (sg.num_parts, len(sg.part_ids()))):
        return state
    return sg.to_padded(np.asarray(state))


class DeviceChecker:
    """Per-part jitted audits over one ShardedGraph (+ optional mesh).

    Builds the flat part-major edge arrays once (they are independent
    of the engines' chunked layouts) and reuses them across audits.
    """

    def __init__(self, sg: ShardedGraph, mesh=None):
        self.sg = sg
        self.mesh = mesh
        arrays = dict(src_slot=sg.src_slot, dst_local=sg.dst_local,
                      vmask=sg.vmask, deg=sg.deg_padded)
        if sg.weighted:
            arrays["weight"] = sg.edge_weight
        if mesh is not None:
            arrays = shard_over_parts(mesh, arrays, sg.num_parts)
        else:
            arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
        self._keys = sorted(arrays)
        self._args = tuple(arrays[k] for k in self._keys)

    # -- shared machinery ----------------------------------------------

    def _run(self, per_part, state, *extra):
        """vmap ``per_part(flat_state, old_p, g, *extra)`` over this
        device's parts (shard_map over the mesh) -> host [num_parts]
        per-part results."""
        sg, keys = self.sg, self._keys

        def core(state, *args):
            gargs, extra_v = args[:len(keys)], args[len(keys):]
            g = dict(zip(keys, gargs))
            if self.mesh is not None:
                # audit: allow(collective-scope) — the acceptance
                # harness re-creates the engines' state exchange on
                # purpose (it verifies placement, it is never priced)
                full = jax.lax.all_gather(state, PARTS_AXIS, tiled=True)
            else:
                full = state
            flat = full.reshape((sg.num_parts * sg.vpad,) +
                                full.shape[2:])
            return jax.vmap(
                lambda old, gp: per_part(flat, old, gp, *extra_v))(
                state, g)

        if self.mesh is not None:
            P = PartitionSpec
            core = jax.shard_map(
                core, mesh=self.mesh,
                in_specs=(P(PARTS_AXIS),) + (P(PARTS_AXIS),) * len(keys)
                         + (P(),) * len(extra),
                out_specs=P(PARTS_AXIS))
        out = jax.jit(core)(self._place_state(state), *self._args,
                            *extra)
        from lux_tpu.parallel.multihost import fetch_global
        return fetch_global(out)

    def _place_state(self, state):
        state = _as_padded(self.sg, state)
        if isinstance(state, jax.Array) and self.mesh is not None:
            return state            # already placed by the engine
        if self.mesh is not None:
            return shard_over_parts(self.mesh, [np.asarray(state)],
                                    self.sg.num_parts)[0]
        return jnp.asarray(state)

    def _edge_pred_counts(self, state, pred):
        """Count edges violating ``pred(src_val, dst_val, weight)``
        per part."""
        sg = self.sg

        def per_part(flat, old, g):
            src_v = jnp.take(flat, g["src_slot"], axis=0)
            valid = g["dst_local"] < sg.vpad
            dst_v = jnp.take(old, jnp.minimum(g["dst_local"],
                                              sg.vpad - 1), axis=0)
            bad = pred(src_v, dst_v, g.get("weight"))
            return jnp.sum((valid & bad).astype(jnp.int32))

        return self._run(per_part, state)

    # -- the audits ----------------------------------------------------

    def sssp(self, state, weighted: bool = False) -> CheckResult:
        """Fixed point: dist[dst] <= dist[src] + w for every edge
        (reference sssp_gpu.cu:792-796, w = 1 in hops mode)."""
        if weighted and not self.sg.weighted:
            raise ValueError("weighted check needs a weighted graph")

        def pred(src_v, dst_v, w):
            # int32 distances (hops, integer weights: apps/sssp.py)
            # add in int32: promoted to float32 a sum past 2^24 would
            # round, and HOP_INF + 2^24 does not wrap
            w = jnp.asarray(1, src_v.dtype) if not weighted \
                else w.astype(src_v.dtype)
            return dst_v > src_v + w

        counts = self._edge_pred_counts(state, pred)
        return CheckResult("sssp triangle inequality (device)",
                           int(counts.sum()), self.sg.ne,
                           per_part=tuple(int(c) for c in counts))

    def components(self, state) -> CheckResult:
        """Fixed point: labels[dst] >= labels[src]
        (reference components_gpu.cu:788)."""
        counts = self._edge_pred_counts(
            state, lambda s, d, w: d < s)
        return CheckResult("components monotonicity (device)",
                           int(counts.sum()), self.sg.ne,
                           per_part=tuple(int(c) for c in counts))

    def pagerank(self, state, tol: float = 1e-6) -> CheckResult:
        """Residual audit: one more (degree-normalized) iteration moves
        every rank by less than ``tol`` (see check.check_pagerank)."""
        from lux_tpu.apps.pagerank import ALPHA
        sg = self.sg

        def per_part(flat, old, g, tol):
            src_v = jnp.take(flat, g["src_slot"], axis=0)
            msgs = jnp.where(g["dst_local"] < sg.vpad, src_v, 0)
            red = segment_reduce(msgs, g["dst_local"], sg.vpad + 1,
                                 "sum")[:sg.vpad]
            pr = (1.0 - ALPHA) / sg.nv + ALPHA * red
            deg = g["deg"].astype(pr.dtype)
            nxt = jnp.where(g["deg"] > 0, pr / jnp.maximum(deg, 1), pr)
            bad = jnp.abs(nxt - old) > tol
            return jnp.sum((bad & g["vmask"]).astype(jnp.int32))

        counts = self._run(per_part, state, jnp.float32(tol))
        return CheckResult(f"pagerank residual(tol={tol}) (device)",
                           int(counts.sum()), self.sg.nv,
                           per_part=tuple(int(c) for c in counts))

    def colfilter(self, state) -> CheckResult:
        """Learned factors must predict ratings no worse than the
        uniform sqrt(1/K) init (see check.check_colfilter).  The init
        prediction is analytically K * (1/K) = 1."""
        sg = self.sg

        def per_part(flat, old, g):
            src_rows = jnp.take(flat, g["src_slot"], axis=0)
            valid = g["dst_local"] < sg.vpad
            dst_rows = jnp.take(old, jnp.minimum(g["dst_local"],
                                                 sg.vpad - 1), axis=0)
            pred = jnp.sum(src_rows * dst_rows, axis=-1)
            w = g["weight"]
            err = jnp.where(valid, w - pred, 0.0)
            err0 = jnp.where(valid, w - 1.0, 0.0)
            return jnp.stack([jnp.sum(err * err),
                              jnp.sum(err0 * err0)])

        sse = self._run(per_part, state)          # [P, 2]
        learned = float(np.sqrt(sse[:, 0].sum() / max(1, sg.ne)))
        init = float(np.sqrt(sse[:, 1].sum() / max(1, sg.ne)))
        bad = int(learned > init + 1e-9)
        return CheckResult("colfilter rmse non-increase (device)",
                           bad, sg.ne)


def check_sssp_device(sg, state, weighted=False, mesh=None):
    return DeviceChecker(sg, mesh).sssp(state, weighted)


def check_components_device(sg, state, mesh=None):
    return DeviceChecker(sg, mesh).components(state)


def check_pagerank_device(sg, state, tol=1e-6, mesh=None):
    return DeviceChecker(sg, mesh).pagerank(state, tol)


def check_colfilter_device(sg, state, mesh=None):
    return DeviceChecker(sg, mesh).colfilter(state)
