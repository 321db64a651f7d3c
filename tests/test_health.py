"""Device-side health watchdog (lux_tpu/health.py, round-9 tentpole).

The acceptance bar: every corruption class is deterministically
DIAGNOSED (a typed HealthError naming the check, part and iteration),
never a silent wrong answer — in particular ``run_until`` on a
NaN-seeded state must keep iterating (and the watchdog variant must
raise), where the old ``res > tol`` predicate exited reporting
convergence on garbage.  Watchdog-on loops must also be bit-identical
to watchdog-off on healthy runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu import health as hw
from lux_tpu import resilience, telemetry
from lux_tpu.apps import pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.engine.program import PullProgram
from lux_tpu.engine.pull import PullEngine
from lux_tpu.engine.push import PushEngine
from lux_tpu.graph import Graph, ShardedGraph
from lux_tpu.parallel.mesh import make_mesh

NOSLEEP = dict(sleep=lambda s: None)


def small_graph(nv=100, ne=700, seed=61):
    src, dst = uniform_random_edges(nv, ne, seed=seed)
    return Graph.from_edges(src, dst, nv)


def synthetic_program(apply_fn, init_val=1.0):
    """A pull program whose next state is a pure function of the old
    one — lets tests drive the residual trajectory exactly."""
    def edge_value(src_val, dst_val, weight):
        return src_val

    def init(sg):
        return np.full((sg.num_parts, sg.vpad), init_val, np.float32)

    return PullProgram(reduce="sum", edge_value=edge_value,
                       apply=lambda old, red, ctx: apply_fn(old),
                       init=init, name="synthetic")


# -- run_until can never report convergence on NaN ---------------------

def test_run_until_nan_residual_is_not_convergence():
    g = small_graph()
    eng = pagerank.build_engine(g, num_parts=2)
    bad = np.array(jax.device_get(eng.init_state()))
    bad[0, 0] = np.nan
    state, it, res = eng.run_until(eng.place(bad), 1e-3, max_iters=5)
    # the old (res > tol) predicate exited at it=1 claiming
    # convergence; the non-finite-safe predicate runs to the cap
    assert int(jax.device_get(it)) == 5
    assert np.isnan(float(jax.device_get(res)))


def test_run_until_health_raises_on_nan_seed():
    g = small_graph()
    eng = pagerank.build_engine(g, num_parts=2)
    bad = np.array(jax.device_get(eng.init_state()))
    bad[1, 0] = np.nan
    _s, it, _res, _rb, _cb, _rp, _cp, h = eng.run_until_health(
        eng.place(bad), 1e-3, max_iters=50)
    assert int(jax.device_get(it)) == 1      # exits AT the trip
    with pytest.raises(hw.HealthError) as ei:
        hw.ensure_ok(h, engine="pull", where="test")
    e = ei.value
    assert "nonfinite_state" in e.checks
    assert "nonfinite_residual" in e.checks
    # the NaN spreads along edges within the first iteration, so the
    # named part is the FIRST with damage, not necessarily the seeded
    assert e.iteration == 0 and e.part >= 0 and e.engine == "pull"
    assert e.count > 0


def test_healthy_run_until_matches_plain():
    g = small_graph()
    eng = pagerank.build_engine(g, num_parts=2)
    s1, it1, res1 = eng.run_until(eng.init_state(), 1e-7,
                                  max_iters=200)
    s2, it2, res2, _rb, _cb, _rp, _cp, h = eng.run_until_health(
        eng.init_state(), 1e-7, max_iters=200)
    assert not hw.ensure_ok(h, engine="pull")["tripped"]
    assert int(jax.device_get(it1)) == int(jax.device_get(it2))
    np.testing.assert_array_equal(np.asarray(jax.device_get(s1)),
                                  np.asarray(jax.device_get(s2)))


# -- pull: parity + each check trips deterministically ----------------

@pytest.mark.parametrize("np_parts,mesh_n", [(2, 0), (8, 8)])
def test_run_health_bitwise_matches_run(np_parts, mesh_n):
    g = small_graph(nv=180, ne=1400, seed=7)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = pagerank.build_engine(g, num_parts=np_parts, mesh=mesh)
    want = eng.unpad(eng.run(eng.init_state(), 10))
    s, it, rb, cb, rbp, cbp, h = eng.run_health(eng.init_state(), 10)
    d = hw.ensure_ok(h, engine="pull")
    assert d == {"engine": "pull", "tripped": False, "flags": []}
    assert int(jax.device_get(it)) == 10
    np.testing.assert_array_equal(eng.unpad(s), want)
    # counters identical to the stats variant's
    s2, rb2, cb2, _rbp2, _cbp2 = eng.run_stats(eng.init_state(), 10)
    np.testing.assert_array_equal(np.asarray(jax.device_get(rb)),
                                  np.asarray(jax.device_get(rb2)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(cb)),
                                  np.asarray(jax.device_get(cb2)))


def test_divergence_trips_after_window():
    """State doubles every iteration: residuals strictly increase and
    blow past the growth bound — DIVERGENCE trips the moment the
    trailing window fills, long before Inf/NaN."""
    g = small_graph(nv=40, ne=200, seed=3)
    sg = ShardedGraph.build(g, 2)
    eng = PullEngine(sg, synthetic_program(lambda old: old * 2,
                                           init_val=1e-3))
    _s, it, _rb, _cb, _rp, _cp, h = eng.run_health(eng.init_state(), 100)
    assert int(jax.device_get(it)) == hw.WINDOW
    with pytest.raises(hw.HealthError) as ei:
        hw.ensure_ok(h, engine="pull", where="test")
    assert ei.value.checks == ["divergence"]
    assert ei.value.iteration == hw.WINDOW - 1


def test_oscillation_trips_after_window():
    """A 4-cycle (0 -> 5 -> 2 -> -3 -> 0) makes the residual series
    5, 3, 5, 3, ...: strictly alternating differences with no net
    decrease — the limit cycle no tolerance will ever end."""
    def cycle(old):
        return jnp.where(old == 0., 5.,
                         jnp.where(old == 5., 2.,
                                   jnp.where(old == 2., -3., 0.)))

    g = small_graph(nv=40, ne=200, seed=3)
    sg = ShardedGraph.build(g, 2)
    eng = PullEngine(sg, synthetic_program(cycle, init_val=0.0))
    _s, it, _rb, _cb, _rp, _cp, h = eng.run_health(eng.init_state(), 100)
    assert int(jax.device_get(it)) == hw.WINDOW
    with pytest.raises(hw.HealthError) as ei:
        hw.ensure_ok(h, engine="pull", where="test")
    assert ei.value.checks == ["oscillation"]


def test_converging_run_never_false_positives():
    """A legitimately converging run (pagerank: residual strictly
    DECREASES) must stay clean far past the window."""
    g = small_graph()
    eng = pagerank.build_engine(g, num_parts=2, health=True)
    s, it, _rb, _cb, _rp, _cp, h = eng.run_health(eng.init_state(),
                                        4 * hw.WINDOW)
    assert not hw.ensure_ok(h, engine="pull")["tripped"]
    assert int(jax.device_get(it)) == 4 * hw.WINDOW


# -- push: parity + NaN labels + frontier stall ------------------------

@pytest.mark.parametrize("np_parts,mesh_n", [(2, 0), (8, 8)])
def test_converge_health_matches_converge(np_parts, mesh_n):
    g = small_graph(nv=180, ne=1400, seed=7)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = sssp.build_engine(g, start_vertex=1, num_parts=np_parts,
                            mesh=mesh)
    l1, a1, it1 = eng.converge(*eng.init_state())
    l2, a2, it2, fsz, fed, _fp, _ep, h = eng.converge_health(*eng.init_state())
    assert not hw.ensure_ok(h, engine="push")["tripped"]
    assert int(jax.device_get(it1)) == int(jax.device_get(it2))
    np.testing.assert_array_equal(np.asarray(jax.device_get(l1)),
                                  np.asarray(jax.device_get(l2)))
    # counters identical to the stats variant's
    _l, _a, _it, fsz2, fed2, _fp2, _ep2 = eng.converge_stats(*eng.init_state())
    np.testing.assert_array_equal(np.asarray(jax.device_get(fsz)),
                                  np.asarray(jax.device_get(fsz2)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(fed)),
                                  np.asarray(jax.device_get(fed2)))


def test_push_nan_labels_trip():
    src, dst, w = uniform_random_edges(100, 800, seed=5, weighted=True)
    # float weights: float32 labels can hold a NaN (integer weights
    # give int32 distances, apps/sssp.py)
    g = Graph.from_edges(src, dst, 100, weights=w.astype(np.float32))
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2,
                            weighted=True, health=True)
    label, active = eng.init_state()
    lb = np.array(jax.device_get(label))
    lb[0, 0] = np.nan
    label, active = eng.place(lb, np.array(jax.device_get(active)))
    _l, _a, _it, _f, _e, _fp, _ep, h = eng.converge_health(label, active)
    with pytest.raises(hw.HealthError) as ei:
        hw.ensure_ok(h, engine="push", where="test")
    assert ei.value.checks == ["nonfinite_state"]
    assert ei.value.iteration == 0 and ei.value.part == 0


def test_push_inf_sentinel_never_trips():
    """+Inf is the legitimate unreached sentinel for weighted sssp —
    a converged run full of them must stay clean."""
    src, dst, w = uniform_random_edges(100, 400, seed=9, weighted=True)
    # vertices 100..119 have no edges at all: provably unreachable
    # (float weights: float32 labels, +inf the sentinel)
    g = Graph.from_edges(src, dst, 120, weights=w.astype(np.float32))
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2,
                            weighted=True, health=True)
    label, _a, _it, _f, _e, _fp, _ep, h = eng.converge_health(*eng.init_state())
    assert not hw.ensure_ok(h, engine="push")["tripped"]
    assert np.isinf(np.asarray(jax.device_get(label))).any()


def test_frontier_stall_trips_and_exits_loop():
    """Truncation livelock: an edge budget below the start hub's
    out-degree makes the sparse queue's processed prefix stick at 0
    forever.  The plain converge spins to max_iters; the watchdog
    variant EXITS at STALL_N consecutive no-progress iterations with
    the frontier_stall diagnosis."""
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    sg = ShardedGraph.build(g, 2)
    prog = sssp.make_program(0)
    eng = PushEngine(sg, prog, edge_budget=1, sparse_threshold=1,
                     health=True)
    label, active = eng.init_state()
    l0, a0, it0 = eng.converge(*eng.init_state(), max_iters=60)
    assert int(jax.device_get(it0)) == 60          # livelocked
    assert int(jax.device_get(jnp.sum(a0))) > 0
    _l, _a, it, _f, _e, _fp, _ep, h = eng.converge_health(label, active,
                                                max_iters=2000)
    assert int(jax.device_get(it)) < 60            # exited early
    with pytest.raises(hw.HealthError) as ei:
        hw.ensure_ok(h, engine="push", where="test")
    assert ei.value.checks == ["frontier_stall"]


# -- wiring: classification, supervisor, telemetry, eng.run ------------

def test_health_error_classifies_fatal():
    e = hw.HealthError("x", checks=["divergence"], iteration=9)
    assert resilience.classify(e) == resilience.FATAL


def test_supervised_run_trips_before_checkpointing_garbage(tmp_path):
    """The watchdog raises at the SEGMENT boundary, before the
    checkpoint save: a diverging run dies fatal-with-diagnosis on the
    first attempt (no retry — the corruption is in the state) and the
    checkpoint on disk stays at the last healthy segment."""
    from lux_tpu import checkpoint as ckpt

    g = small_graph(nv=40, ne=200, seed=3)
    sg = ShardedGraph.build(g, 2)
    eng = PullEngine(sg, synthetic_program(lambda old: old * 2,
                                           init_val=1e-3),
                     health=True)
    path = str(tmp_path / "div.npz")
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        with pytest.raises(hw.HealthError):
            resilience.supervised_run(
                eng, 40, path, segment=4,
                policy=resilience.RetryPolicy(retries=3, **NOSLEEP))
    assert ev.counts().get("health_trip") == 1
    assert ev.counts().get("failure") == 1     # fatal: exactly one
    trip = [e for e in ev.events if e["kind"] == "health_trip"][0]
    assert trip["flags"] == ["divergence"]
    # only the first (healthy, iterations 0-3) segment was saved; the
    # residual window is THREADED across segments (segment=4 is
    # shorter than the window), so divergence still trips the
    # iteration the window fills — globally numbered via the tick
    _leaves, meta = ckpt.load(path)
    assert meta["iter"] == 4
    assert trip["iteration"] == hw.WINDOW - 1


def test_engine_run_uses_watchdog_when_enabled():
    g = small_graph(nv=40, ne=200, seed=3)
    sg = ShardedGraph.build(g, 2)
    eng = PullEngine(sg, synthetic_program(lambda old: old * 2,
                                           init_val=1e-3),
                     health=True)
    with pytest.raises(hw.HealthError):
        eng.run(eng.init_state(), 100)
    # push side: eng.run on a livelocked engine diagnoses instead of
    # spinning (frontier_stall), via the same run() entry point
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g2 = Graph.from_edges(src, dst, 200)
    sg2 = ShardedGraph.build(g2, 2)
    e2 = PushEngine(sg2, sssp.make_program(0), edge_budget=1,
                    sparse_threshold=1, health=True)
    with pytest.raises(hw.HealthError):
        e2.run(max_iters=2000)


def test_timed_helpers_emit_health_digest():
    from lux_tpu.timing import timed_converge, timed_fused_run

    g = small_graph()
    eng = pagerank.build_engine(g, num_parts=2, health=True)
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        _state, elapsed = timed_fused_run(eng, 5, repeats=2)
    assert len(elapsed) == 2
    hs = [e for e in ev.events if e["kind"] == "health"]
    assert len(hs) == 1 and hs[0]["tripped"] is False \
        and hs[0]["engine"] == "pull" and hs[0]["iters"] == 5

    e2 = sssp.build_engine(g, start_vertex=0, num_parts=2, health=True)
    ev2 = telemetry.EventLog()
    with telemetry.use(events=ev2):
        _labels, iters, _el = timed_converge(e2, repeats=1)
    hs = [e for e in ev2.events if e["kind"] == "health"]
    assert len(hs) == 1 and hs[0]["tripped"] is False \
        and hs[0]["engine"] == "push" and hs[0]["iters"] == iters


def test_word_decode_roundtrip():
    h = np.array([hw.DIVERGENCE | hw.NONFINITE_RESIDUAL, 12, 3, 7,
                  np.float32(2.5).view(np.int32), 0], np.int32)
    d = hw.digest(h, engine="pull", base_iter=100)
    assert d["tripped"] and d["iteration"] == 112 and d["part"] == 3
    assert d["flags"] == ["nonfinite_residual", "divergence"]
    assert d["residual"] == 2.5 and d["count"] == 7
