"""In-loop telemetry (lux_tpu/telemetry.py): device-side iteration
counters against stepwise/NumPy oracles, the structured event log, and
the cross-layer wiring (segmented drivers, supervisor, timing helpers).

The counter contract under test is the acceptance bar of the round-7
ISSUE: the fused run's per-iteration frontier sizes / residuals must
equal what the old stepwise -verbose path printed — computed here by
actually stepping the engines one compiled iteration at a time.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from lux_tpu import telemetry
from lux_tpu.apps import components, pagerank, sssp
from lux_tpu.convert import rmat_edges, uniform_random_edges
from lux_tpu.engine.pull import PullEngine
from lux_tpu.graph import Graph
from lux_tpu.parallel.mesh import make_mesh


def small_graph(nv=180, ne=1400, seed=7, weighted=False):
    if weighted:
        src, dst, w = uniform_random_edges(nv, ne, seed=seed,
                                           weighted=True)
        return Graph.from_edges(src, dst, nv, weights=w)
    src, dst = uniform_random_edges(nv, ne, seed=seed)
    return Graph.from_edges(src, dst, nv)


def stepwise_push_series(eng):
    """The old stepwise -verbose path: frontier size after each
    compiled step, plus each iteration's entering-frontier out-edges
    from the full graph's degrees (the NumPy side of the oracle)."""
    deg = np.asarray(eng.sg.deg_padded)
    label, active = eng.init_state()
    fronts, edges = [], []
    cnt = int(jax.device_get(np.sum(np.asarray(active))))
    while cnt > 0:
        act_np = np.asarray(jax.device_get(active))
        edges.append(int(deg[act_np].sum()))
        label, active, c = eng.step(label, active)
        cnt = int(jax.device_get(c))
        fronts.append(cnt)
    return fronts, edges


@pytest.mark.parametrize("np_parts,mesh_n", [(1, 0), (8, 8)])
def test_push_classic_counters_match_stepwise(np_parts, mesh_n):
    g = small_graph()
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = sssp.build_engine(g, start_vertex=1, num_parts=np_parts,
                            mesh=mesh)
    fronts, edges = stepwise_push_series(eng)

    label, active = eng.init_state()
    l2, a2, it, fsz, fed, fszp, fedp = eng.converge_stats(label, active)
    it = int(jax.device_get(it))
    assert it == len(fronts)
    assert np.asarray(fsz)[:it].tolist() == fronts
    assert np.asarray(fed)[:it].tolist() == edges
    # past-the-run entries stay zero, and the labels are the oracle's
    assert not np.asarray(fsz)[it:].any()
    dist = eng.unpad(l2)
    want = sssp.reference_sssp(g, start_vertex=1)
    reach = ~sssp.unreachable(dist)
    np.testing.assert_array_equal(dist[reach], want[reach])


@pytest.mark.parametrize("np_parts,mesh_n", [(1, 0), (8, 8)])
def test_components_counters_match_stepwise(np_parts, mesh_n):
    s, d = small_graph(seed=9).edge_arrays()
    g = Graph.from_edges(*components.symmetrize(s, d), 180)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = components.build_engine(g, num_parts=np_parts, mesh=mesh)
    fronts, edges = stepwise_push_series(eng)
    label, active = eng.init_state()
    _l, _a, it, fsz, fed, fszp, fedp = eng.converge_stats(label, active)
    it = int(jax.device_get(it))
    assert np.asarray(fsz)[:it].tolist() == fronts
    assert np.asarray(fed)[:it].tolist() == edges


def delta_bucket_fronts(g, start, delta, ident):
    """NumPy delta-stepping, written from the schedule's definition:
    relax the active vertices under the bucket bound ``B``; when none
    is left, move ``B`` to the least active label + ``delta`` (strictly
    past it).  Returns the front size entering each relax, and the
    labels."""
    src, dst = g.edge_arrays()
    w = np.asarray(g.weights)
    ldt = np.asarray(ident).dtype

    def advance(am):
        nb = am + np.asarray(delta, ldt)
        if np.issubdtype(ldt, np.inexact):
            nb = max(nb, np.nextafter(am, np.asarray(np.inf, ldt)))
        return np.asarray(nb, ldt)

    label = np.full(g.nv, ident, ldt)
    label[start] = 0
    active = np.zeros(g.nv, bool)
    active[start] = True
    bound = advance(label[active].min())
    fronts = []
    while active.any():
        front = active & (label < bound)
        if not front.any():
            bound = advance(label[active].min())
            continue
        fronts.append(int(front.sum()))
        e = front[src]
        new = label.copy()
        np.minimum.at(new, dst[e], label[src[e]] + w[e].astype(ldt))
        active = (active & ~front) | (new < label)
        label = new
    return fronts, label


def test_push_delta_counters_match_bucket_schedule():
    """Delta engines record each relax step's bucket-front size:
    ``converge_stats``' series against the NumPy bucket schedule."""
    g = small_graph(weighted=True)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=1,
                            weighted=True, delta="auto")
    label, _a, it, fsz, _fed, _fp, _ep = eng.converge_stats(
        *eng.init_state())
    it = int(jax.device_get(it))
    fronts, want = delta_bucket_fronts(g, 0, eng.delta,
                                       eng.program.identity)
    assert len(fronts) > 3
    assert np.asarray(fsz)[:it].tolist() == fronts
    np.testing.assert_array_equal(eng.unpad(label), want)


@pytest.mark.parametrize("np_parts,mesh_n", [(1, 0), (8, 8)])
def test_pull_counters_match_stepwise(np_parts, mesh_n):
    g = small_graph(seed=11)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = pagerank.build_engine(g, num_parts=np_parts, mesh=mesh)
    prev = np.asarray(jax.device_get(eng.init_state())).copy()
    res_oracle, chg_oracle = [], []
    s = eng.init_state()
    for _ in range(5):
        s = eng.step(s)
        cur = np.asarray(jax.device_get(s)).copy()
        d = np.abs(cur.astype(np.float32) - prev.astype(np.float32))
        res_oracle.append(float(d.max()))
        chg_oracle.append(int((d > 0).sum()))
        prev = cur

    s2, rb, cb, rbp, cbp = eng.run_stats(eng.init_state(), 5)
    np.testing.assert_allclose(np.asarray(rb)[:5], res_oracle,
                               rtol=1e-6)
    assert np.asarray(cb)[:5].tolist() == chg_oracle
    np.testing.assert_array_equal(np.asarray(jax.device_get(s2)), prev)


def test_pull_run_until_stats_matches_run_until():
    g = small_graph(seed=13)
    eng = pagerank.build_engine(g, num_parts=2)
    s1, it1, res1 = eng.run_until(eng.init_state(), 1e-6,
                                  max_iters=50)
    s2, it2, res2, rb, cb, rbp, cbp = eng.run_until_stats(
        eng.init_state(), 1e-6, max_iters=50)
    it1, it2 = int(jax.device_get(it1)), int(jax.device_get(it2))
    assert it1 == it2
    assert float(jax.device_get(res1)) == float(jax.device_get(res2))
    # the residual series ends exactly at the convergence residual,
    # and every earlier entry is above the tolerance
    rbn = np.asarray(rb)[:it2]
    assert rbn[-1] == pytest.approx(float(jax.device_get(res2)))
    assert (rbn[:-1] > 1e-6).all()
    np.testing.assert_array_equal(np.asarray(jax.device_get(s1)),
                                  np.asarray(jax.device_get(s2)))


def test_push_verbose_replays_counters(capsys):
    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    fronts, _ = stepwise_push_series(eng)
    eng2 = sssp.build_engine(g, start_vertex=1, num_parts=1)
    _labels, it = eng2.run(verbose=True)
    out = capsys.readouterr().out
    want = [f"iter {i}: frontier={f}" for i, f in enumerate(fronts, 1)]
    got = [ln for ln in out.splitlines() if ln.startswith("iter ")]
    assert [ln.split(" edges")[0] for ln in got] == want


def test_stats_cap_truncation():
    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    eng.stats_cap = 2     # read lazily when converge_stats compiles
    label, active = eng.init_state()
    _l, _a, it, fsz, fed, fszp, fedp = eng.converge_stats(label, active)
    it = int(jax.device_get(it))
    assert it > 2 and fsz.shape == (2,)
    st = telemetry.IterStats()
    st.extend_push(fsz, fed, it)
    assert st.truncated and len(st.frontier) == 2
    assert "truncated" in list(st.replay_lines())[-1]


def test_segmented_accumulation_matches_unsegmented():
    """Slice boundaries must be invisible in the counter series (the
    supervised/budgeted paths run through converge_segments)."""
    from lux_tpu.segmented import converge_segments, run_segments

    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    label, active = eng.init_state()
    _l, _a, it, fsz, _fed, _fp, _ep = eng.converge_stats(label, active)
    it = int(jax.device_get(it))

    st = telemetry.IterStats()
    ev = telemetry.EventLog()
    with telemetry.use(events=ev, iter_stats=st):
        label, active = eng.init_state()
        _l2, _a2, total = converge_segments(eng, label, active,
                                            segment=2)
    assert total == it
    assert st.frontier == np.asarray(fsz)[:it].tolist()
    segs = [e for e in ev.events if e["kind"] == "segment"]
    assert sum(e["iters"] for e in segs) == it
    assert all(e["engine"] == "push" for e in segs)

    peng = pagerank.build_engine(g, num_parts=1)
    _s, rb, cb, _rbp, _cbp = peng.run_stats(peng.init_state(), 6)
    st2 = telemetry.IterStats()
    with telemetry.use(iter_stats=st2):
        run_segments(peng, peng.init_state(), 6, segment=4)
    np.testing.assert_allclose(st2.residual, np.asarray(rb)[:6],
                               rtol=1e-6)
    assert st2.changed == np.asarray(cb)[:6].tolist()


def test_timed_helpers_emit_and_record(tmp_path):
    from lux_tpu.timing import timed_converge, timed_fused_run

    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    st = telemetry.IterStats()
    ev = telemetry.EventLog(str(tmp_path / "ev.jsonl"))
    with telemetry.use(events=ev, iter_stats=st):
        _labels, it, elapsed = timed_converge(eng, repeats=2)
    assert len(elapsed) == 2 and len(st.frontier) == it
    runs = [e for e in ev.events if e["kind"] == "timed_run"]
    assert [r["repeat"] for r in runs] == [0, 1]
    assert [r["seconds"] for r in runs] == \
        [round(e, 6) for e in elapsed]
    # the JSONL on disk is the same stream
    lines = [json.loads(s) for s in
             (tmp_path / "ev.jsonl").read_text().splitlines()]
    assert [ln["kind"] for ln in lines] == \
        [e["kind"] for e in ev.events]

    peng = pagerank.build_engine(g, num_parts=1)
    st2 = telemetry.IterStats()
    with telemetry.use(iter_stats=st2):
        timed_fused_run(peng, 4, repeats=1)
    assert st2.kind == "pull" and len(st2.residual) == 4


def test_supervised_run_report_carries_counters(tmp_path):
    from lux_tpu import resilience

    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    st = telemetry.IterStats()
    ev = telemetry.EventLog()
    with telemetry.use(events=ev, iter_stats=st):
        _label, _active, total, report = resilience.supervised_converge(
            eng, str(tmp_path / "ck.npz"), segment=2)
    assert report.counters is not None
    assert report.counters["kind"] == "push"
    assert report.counters["iters"] == total == len(st.frontier)
    assert report.as_dict()["counters"] == report.counters
    kinds = ev.counts()
    assert kinds.get("segment") and kinds.get("checkpoint_save")


def test_counters_exact_through_crash_resume(tmp_path):
    """Counters append only after the segment hook (checkpoint save)
    survives: a crash in the save window re-runs the slice on resume,
    and the accumulated series must NOT double-count it."""
    from lux_tpu import faults, resilience

    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=1)
    label, active = eng.init_state()
    _l, _a, it, fsz, _fed, _fp, _ep = eng.converge_stats(label, active)
    it = int(jax.device_get(it))
    ref = np.asarray(fsz)[:it].tolist()

    eng2 = sssp.build_engine(g, start_vertex=1, num_parts=1)
    plan = faults.FaultPlan.seeded(seed=3, n=8, p_crash=0.5)
    st = telemetry.IterStats()
    with telemetry.use(iter_stats=st):
        _lbl, _act, total, report = resilience.supervised_converge(
            eng2, str(tmp_path / "ck.npz"), segment=2, faults=plan,
            policy=resilience.RetryPolicy(retries=8, backoff_s=0.0))
    assert report.attempts > 1, "no injected crash fired"
    assert total == it
    assert st.frontier == ref


# -- round 13: per-part counters vs NumPy per-part oracles -------------
#    (sum-over-parts must BITWISE-equal the scalar counter series; the
#    engines reduce the same device-side values part-first)

def per_part_push_oracle(eng):
    """NumPy per-part oracle: stepwise frontier size and entering
    out-edges PER PART — the decomposition the fused per-part
    buffers must reproduce exactly."""
    deg = np.asarray(eng.sg.deg_padded)
    label, active = eng.init_state()
    fronts_p, edges_p = [], []
    cnt = int(jax.device_get(np.sum(np.asarray(active))))
    while cnt > 0:
        act = np.asarray(jax.device_get(active))
        edges_p.append([int(deg[p][act[p]].sum())
                        for p in range(act.shape[0])])
        label, active, c = eng.step(label, active)
        cnt = int(jax.device_get(c))
        act = np.asarray(jax.device_get(active))
        fronts_p.append([int(act[p].sum())
                         for p in range(act.shape[0])])
    return fronts_p, edges_p


def per_part_pull_oracle(eng, iters):
    """NumPy per-part oracle: stepwise max-abs residual and
    changed-vertex count per part."""
    prev = np.asarray(jax.device_get(eng.init_state())).copy()
    res_p, chg_p = [], []
    s = eng.init_state()
    for _ in range(iters):
        s = eng.step(s)
        cur = np.asarray(jax.device_get(s)).copy()
        d = np.abs(cur.astype(np.float32) - prev.astype(np.float32))
        dp = d.reshape(d.shape[0], -1)
        res_p.append(dp.max(axis=1).tolist())
        chg_p.append([int((row > 0).sum()) for row in dp])
        prev = cur
    return res_p, chg_p


@pytest.mark.parametrize("np_parts,mesh_n", [(4, 0), (8, 8)])
def test_push_per_part_counters_match_oracle(np_parts, mesh_n):
    """converge_stats per-part buffers vs the NumPy per-part oracle,
    on 1 device (mesh_n=0) and the full 8-virtual-device mesh; the
    scalar series must be the bitwise sum of the per-part rows."""
    g = small_graph()
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = sssp.build_engine(g, start_vertex=1, num_parts=np_parts,
                            mesh=mesh)
    fronts_p, edges_p = per_part_push_oracle(eng)
    label, active = eng.init_state()
    _l, _a, it, fsz, fed, fszp, fedp = eng.converge_stats(label,
                                                          active)
    it = int(jax.device_get(it))
    fszp = np.asarray(jax.device_get(fszp))
    fedp = np.asarray(jax.device_get(fedp))
    assert fszp.shape == (eng.stats_cap, np_parts)
    assert fszp[:it].tolist() == fronts_p
    assert fedp[:it].tolist() == edges_p
    # sum-over-parts == the scalar series, BITWISE
    np.testing.assert_array_equal(
        fszp[:it].sum(axis=1, dtype=np.int64),
        np.asarray(jax.device_get(fsz))[:it])
    np.testing.assert_array_equal(
        fedp[:it].astype(np.uint64).sum(axis=1).astype(np.uint32),
        np.asarray(jax.device_get(fed))[:it])
    assert not fszp[it:].any() and not fedp[it:].any()


@pytest.mark.parametrize("np_parts,mesh_n", [(4, 0), (8, 8)])
def test_pull_per_part_counters_match_oracle(np_parts, mesh_n):
    g = small_graph(seed=11)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = pagerank.build_engine(g, num_parts=np_parts, mesh=mesh)
    res_p, chg_p = per_part_pull_oracle(eng, 5)
    _s, rb, cb, rbp, cbp = eng.run_stats(eng.init_state(), 5)
    rbp = np.asarray(jax.device_get(rbp))
    cbp = np.asarray(jax.device_get(cbp))
    np.testing.assert_array_equal(rbp[:5], np.asarray(res_p,
                                                      np.float32))
    assert cbp[:5].tolist() == chg_p
    # max/sum over parts == the scalar series, BITWISE
    np.testing.assert_array_equal(rbp[:5].max(axis=1),
                                  np.asarray(jax.device_get(rb))[:5])
    np.testing.assert_array_equal(
        cbp[:5].astype(np.uint64).sum(axis=1).astype(np.uint32),
        np.asarray(jax.device_get(cb))[:5])


def test_per_part_counters_ride_health_variants():
    """The *_health loop variants carry the same per-part counters
    (bitwise-equal to the *_stats variants'): converge_health,
    run_health and run_until_health vs their stats twins on the same
    per_part oracle contract."""
    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=4)
    _l, _a, it, _f, _e, fszp, fedp = eng.converge_stats(
        *eng.init_state())
    _l2, _a2, _it2, _f2, _e2, fszp2, fedp2, h = eng.converge_health(
        *eng.init_state())
    np.testing.assert_array_equal(np.asarray(jax.device_get(fszp)),
                                  np.asarray(jax.device_get(fszp2)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(fedp)),
                                  np.asarray(jax.device_get(fedp2)))

    peng = pagerank.build_engine(g, num_parts=4)
    _s, _rb, _cb, rbp, cbp = peng.run_stats(peng.init_state(), 6)
    _s2, _it, _rb2, _cb2, rbp2, cbp2, _h = peng.run_health(
        peng.init_state(), 6)
    np.testing.assert_array_equal(np.asarray(jax.device_get(rbp)),
                                  np.asarray(jax.device_get(rbp2)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(cbp)),
                                  np.asarray(jax.device_get(cbp2)))

    _s3, it3, _r3, rb3, cb3, rbp3, cbp3 = peng.run_until_stats(
        peng.init_state(), 1e-6, max_iters=6)
    _s4, it4, _r4, _rb4, _cb4, rbp4, cbp4, _h4 = \
        peng.run_until_health(peng.init_state(), 1e-6, max_iters=6)
    assert int(jax.device_get(it3)) == int(jax.device_get(it4))
    np.testing.assert_array_equal(np.asarray(jax.device_get(rbp3)),
                                  np.asarray(jax.device_get(rbp4)))
    np.testing.assert_array_equal(np.asarray(jax.device_get(cbp3)),
                                  np.asarray(jax.device_get(cbp4)))


def test_iter_stats_imbalance_digest():
    """IterStats per-part accumulation: part totals, the max/mean
    imbalance index, the summary fields and the bench digest."""
    st = telemetry.IterStats()
    fsz = np.asarray([3, 2], np.int32)
    fed = np.asarray([30, 10], np.uint32)
    fszp = np.asarray([[2, 1], [1, 1]], np.int32)
    fedp = np.asarray([[25, 5], [5, 5]], np.uint32)
    st.extend_push(fsz, fed, 2, fszp, fedp)
    assert st.num_parts() == 2
    assert st.part_totals() == [30, 10]          # edges per part
    assert st.imbalance() == pytest.approx(30 / 20)
    s = st.summary()
    assert s["parts"] == 2 and s["parts_edges"] == [30, 10]
    assert s["imbalance"] == pytest.approx(1.5)
    assert sum(s["parts_edges"]) == s["edges_sum"]    # bitwise
    d = st.imbalance_digest()
    assert d == {"kind": "push", "index": 1.5, "parts": [30, 10]}
    lines = list(st.parts_lines())
    assert "imbalance 1.500" in lines[0]
    assert any("part 0: 30" in ln for ln in lines)
    # per-part-free runs keep the legacy digest shape
    st2 = telemetry.IterStats()
    st2.extend_push(fsz, fed, 2)
    assert st2.part_totals() is None
    assert st2.imbalance_digest() is None
    assert "parts" not in st2.summary()


def test_segmented_per_part_accumulation_matches_unsegmented():
    """Per-part series must be boundary-invisible exactly like the
    scalar series (the supervised drivers fetch the part buffers once
    per segment)."""
    from lux_tpu.segmented import converge_segments

    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=4)
    label, active = eng.init_state()
    _l, _a, it, _f, _e, fszp, fedp = eng.converge_stats(label, active)
    it = int(jax.device_get(it))
    st = telemetry.IterStats()
    with telemetry.use(iter_stats=st):
        label, active = eng.init_state()
        converge_segments(eng, label, active, segment=2)
    assert st.frontier_parts == \
        np.asarray(jax.device_get(fszp))[:it].tolist()
    assert st.edges_parts == \
        np.asarray(jax.device_get(fedp))[:it].tolist()
    # and the digest's bitwise contract holds over the whole run
    s = st.summary()
    assert sum(s["parts_edges"]) == s["edges_sum"]


def test_event_log_and_null_handle():
    ev = telemetry.EventLog()
    ev.emit("header", nv=4)
    ev.emit("segment", engine="pull", seconds=0.5)
    assert ev.counts() == {"header": 1, "segment": 1}
    # the null handle swallows emits and reports no sinks
    assert telemetry.current().emit("anything") is None
    assert telemetry.current().iter_stats is None
    # nested scopes restore the previous handle
    with telemetry.use(events=ev) as tel:
        assert telemetry.current() is tel
    assert telemetry.current().events is None


def test_event_log_rotation_single_process(tmp_path):
    """Round-17 bounded EventLog: size-triggered rotation shifts
    generations (.1 -> .2, live -> .1), stamps each fresh live file
    with a log_rotate event, drops generations past the window, and
    telemetry.rotated_paths lists the surviving set oldest-first
    with per-stream tm still monotone across the concatenation."""
    import os

    path = str(tmp_path / "ev.jsonl")
    ev = telemetry.EventLog(path, rotate_bytes=4000)
    pad = "x" * 200
    for i in range(60):
        ev.emit("mark", i=i, pad=pad)
    ev.close()
    assert ev.rotations >= 2
    paths = telemetry.rotated_paths(path)
    assert paths == [f"{path}.2", f"{path}.1", path]
    assert all(os.path.exists(p) for p in paths)
    events = []
    for p in paths:
        events += [json.loads(ln)
                   for ln in open(p).read().splitlines()]
    marks = [e["i"] for e in events if e["kind"] == "mark"]
    # oldest generations beyond the window dropped; the kept tail is
    # contiguous and ends at the newest event
    assert marks == list(range(marks[0], 60))
    rots = [e for e in events if e["kind"] == "log_rotate"]
    assert rots and all(r["path"] == path for r in rots)
    tms = [e["tm"] for e in events]
    assert tms == sorted(tms)
    # in-memory view complete while under the MEM_KEEP bound (a
    # rotation only trims once the list outgrows it)
    assert len(ev.events) < ev.MEM_KEEP
    assert [e["i"] for e in ev.events
            if e["kind"] == "mark"] == list(range(60))

    with pytest.raises(ValueError):
        telemetry.EventLog(path, rotate_bytes=0)
    with pytest.raises(ValueError):
        telemetry.EventLog(path, rotate_bytes=100, generations=0)
    # a plain (never-rotated) path is its own one-element set
    lone = str(tmp_path / "lone.jsonl")
    assert telemetry.rotated_paths(lone) == [lone]


# ---- PR 24: the span primitive (telemetry.span / mark / spans) --------

def _since(mark_id):
    """Ring records made after the record with id ``mark_id``."""
    return [r for r in telemetry.spans() if r["id"] > mark_id]


def _ring_tip():
    telemetry.mark("test.tip")
    return telemetry.spans()[-1]["id"]


def test_span_nesting_ids_and_parents():
    tip = _ring_tip()
    with telemetry.span("outer", k=1) as a:
        with telemetry.span("inner") as b:
            b.count(bytes=12)
        with telemetry.span("inner") as c:
            telemetry.mark("note", hits=3)
    recs = {r["id"]: r for r in _since(tip)}
    assert a.id < b.id < c.id and set(recs) >= {a.id, b.id, c.id}
    assert recs[a.id]["parent"] == 0
    assert recs[b.id]["parent"] == recs[c.id]["parent"] == a.id
    assert recs[b.id]["counts"] == {"bytes": 12}
    assert recs[a.id]["counts"] == {"k": 1}
    note = next(r for r in recs.values() if r["name"] == "note")
    assert note["parent"] == c.id and note["t0"] == note["t1"]
    assert note["counts"] == {"hits": 3}
    # children lie inside the parent on the one clock
    for kid in (b.id, c.id):
        assert recs[a.id]["t0"] <= recs[kid]["t0"] <= recs[kid]["t1"] \
            <= recs[a.id]["t1"]
    # records land in exit order: children before their parent
    order = [r["id"] for r in _since(tip)]
    assert order.index(b.id) < order.index(c.id) < order.index(a.id)


def test_span_on_another_thread_is_a_root():
    import threading
    tip = _ring_tip()
    seen = {}

    def work():
        with telemetry.span("thread.root") as s:
            with telemetry.span("thread.child") as k:
                seen["ids"] = (s.id, k.id)

    with telemetry.span("main.root") as m:
        t = threading.Thread(target=work)
        t.start()
        t.join()
    recs = {r["id"]: r for r in _since(tip)}
    root, child = seen["ids"]
    assert recs[root]["parent"] == 0          # NOT under main.root
    assert recs[child]["parent"] == root
    assert recs[m.id]["parent"] == 0
    assert len({m.id, root, child}) == 3


def test_span_ring_is_bounded_and_drops_the_oldest():
    for _ in range(telemetry.SPAN_RING + 10):
        telemetry.mark("fill")
    recs = telemetry.spans()
    assert len(recs) == telemetry.SPAN_RING
    ids = [r["id"] for r in recs]
    assert ids == sorted(ids) and ids[-1] - ids[0] == len(ids) - 1
    telemetry.mark("one.more")
    again = telemetry.spans()
    assert len(again) == telemetry.SPAN_RING
    assert again[0]["id"] == ids[1] and again[-1]["name"] == "one.more"


def test_span_event_reaches_an_observer_exactly_once():
    got = []
    telemetry.add_observer(got.append)
    try:
        with telemetry.span("obs.outer", n=2) as s:
            pass
    finally:
        telemetry.remove_observer(got.append)
    evs = [e for e in got if e["kind"] == "span"]
    assert len(evs) == 1
    ev = evs[0]
    assert (ev["name"], ev["id"], ev["parent"]) == ("obs.outer", s.id, 0)
    assert ev["counts"] == {"n": 2} and ev["t1"] >= ev["t0"]
    assert ev["seconds"] == pytest.approx(ev["t1"] - ev["t0"], abs=2e-6)
    # ... and through an event sink scoped with use()
    log = telemetry.EventLog()
    with telemetry.use(events=log):
        with telemetry.span("sink.span"):
            pass
    assert [e["name"] for e in log.events if e["kind"] == "span"] \
        == ["sink.span"]


def test_span_builds_no_event_without_sink_or_observer(monkeypatch):
    assert not telemetry._OBSERVERS
    assert telemetry.current().events is None

    def boom(kind, fields):
        raise AssertionError(f"built a {kind} event nobody asked for")

    monkeypatch.setattr(telemetry, "make_event", boom)
    tip = telemetry.spans()[-1]["id"] if telemetry.spans() else 0
    with telemetry.span("quiet"):
        telemetry.mark("quiet.mark")
    assert [r["name"] for r in _since(tip)] == ["quiet.mark", "quiet"]


def test_device_scalar_count_is_fetched_at_snapshot_not_at_the_call():
    class Lazy:
        fetched = 0

        def item(self):
            Lazy.fetched += 1
            return 7

    telemetry.mark("lazy.mark", iters=Lazy())
    with telemetry.span("lazy.span") as s:
        s.count(ns=Lazy())
    assert Lazy.fetched == 0
    recs = telemetry.spans()
    assert Lazy.fetched == 2
    assert recs[-2]["counts"] == {"iters": 7}
    assert recs[-1]["counts"] == {"ns": 7}
    telemetry.spans()                   # written back: fetched once
    assert Lazy.fetched == 2
    # a real device scalar: un-fetched in the ring, an int in the
    # snapshot; an event built meanwhile carries it only if ready
    x = jax.numpy.int32(41) + 1
    telemetry.mark("dev.mark", iters=x)
    assert telemetry._RING[-1]["counts"]["iters"] is x
    assert telemetry.spans()[-1]["counts"] == {"iters": 42}


def test_later_device_counts_settle_the_ready_ones_before_them():
    """A long-lived server must not pin a ring full of device
    scalars: a record that brings device counts settles every earlier
    one that needs no waiting, and never waits for one in flight."""
    class Dev:
        fetched = 0

        def __init__(self, ready):
            self.ready = ready

        def is_ready(self):
            return self.ready

        def item(self):
            Dev.fetched += 1
            return 5

    telemetry.spans()                       # nothing left unsettled
    first, second = Dev(True), Dev(False)
    telemetry.mark("dev.a", iters=first)
    assert Dev.fetched == 0                 # never at its own call
    telemetry.mark("dev.b", iters=second)
    assert Dev.fetched == 1                 # dev.a was ready
    telemetry.mark("dev.c", iters=Dev(True))
    assert Dev.fetched == 1                 # dev.b in flight: no wait
    assert len(telemetry._UNSETTLED) == 2
    assert [r["counts"] for r in telemetry.spans()[-3:]] \
        == [{"iters": 5}] * 3
    assert not telemetry._UNSETTLED


def test_a_count_on_a_lost_device_reads_none_and_breaks_no_reader():
    class Lost:
        def item(self):
            raise RuntimeError("device went away")

    telemetry.mark("lost.mark", iters=Lost(), kept=3)
    telemetry.mark("after.lost")
    recs = telemetry.spans()
    assert recs[-2]["counts"] == {"iters": None, "kept": 3}
    assert recs[-1]["name"] == "after.lost"


def test_span_body_that_raises_still_records_and_reraises():
    tip = _ring_tip()
    with pytest.raises(KeyError):
        with telemetry.span("raises.outer"):
            with telemetry.span("raises.inner"):
                raise KeyError("x")
    assert [r["name"] for r in _since(tip)] \
        == ["raises.inner", "raises.outer"]
    # the enclosing-span context is restored: the next span is a root
    with telemetry.span("after") as s:
        pass
    assert telemetry.spans()[-1]["parent"] == 0 and s.parent == 0


def test_empty_span_costs_under_a_generous_ceiling():
    import time
    n = 5000
    for _ in range(200):                # warm the paths
        with telemetry.span("cost"):
            pass
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("cost"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 50e-6, f"{best * 1e6:.1f} us per empty span"


def test_mark_with_seconds_is_a_record_that_ends_now():
    telemetry.mark("timed.mark", seconds=0.25, fun="f")
    r = telemetry.spans()[-1]
    assert r["t1"] - r["t0"] == pytest.approx(0.25)
    assert r["counts"] == {"fun": "f"}


def test_trace_export_nests_span_events_and_validates():
    import time

    from lux_tpu import tracing
    log = telemetry.EventLog()
    with telemetry.use(events=log):
        log.emit("run_start", app="spans")
        with telemetry.span("relabel") as b:
            with telemetry.span("relabel.deal", tiles=3):
                time.sleep(0.001)
            with telemetry.span("relabel.rebuild_csc"):
                telemetry.mark("jit.compile")
                time.sleep(0.001)
        with telemetry.span("state.init"):
            time.sleep(0.001)   # under a microsecond draws as a mark
        log.emit("run_done", seconds=0.0)
    trace = tracing.trace_export(log.events)
    assert tracing.validate_trace(trace) == []
    drawn = [e for e in trace["traceEvents"] if e.get("cat") == "span"]
    by = {e["name"]: e for e in drawn}
    assert set(by) == {"relabel", "relabel.deal",
                       "relabel.rebuild_csc", "state.init"}
    assert by["relabel.deal"]["args"]["parent"] == b.id
    assert by["relabel.deal"]["args"]["tiles"] == 3
    assert len({e["tid"] for e in drawn}) == 1      # one lane, nested
    for kid in ("relabel.deal", "relabel.rebuild_csc"):
        assert by["relabel"]["ts"] <= by[kid]["ts"] + 2
        assert by[kid]["ts"] + by[kid]["dur"] \
            <= by["relabel"]["ts"] + by["relabel"]["dur"] + 2
    marks = [e for e in trace["traceEvents"] if e.get("ph") == "i"
             and e["name"] == "jit.compile"]
    assert len(marks) == 1 and marks[0]["tid"] == by["relabel"]["tid"]


def test_scripts_accept_the_span_kind(tmp_path):
    import io
    import sys
    sys.path.insert(0, "scripts")
    import check_bench
    import events_summary
    path = str(tmp_path / "ev.jsonl")
    with telemetry.EventLog(path) as log, telemetry.use(events=log):
        for _ in range(2):
            with telemetry.span("state.fetch", bytes=4):
                pass
    events = list(check_bench.iter_event_lines(path))
    assert [ev["kind"] for _w, ev in events] == ["span", "span"]
    assert check_bench.check_event_lines(path, events) == []
    bad = [("line 1", dict(events[0][1], t1=events[0][1]["t0"] - 1))]
    assert check_bench.check_event_lines(path, bad)
    out = io.StringIO()
    assert "span" in events_summary.KNOWN
    assert events_summary.render_run([ev for _w, ev in events],
                                     out=out) == []
    assert "program spans: 2 record(s)" in out.getvalue()
    assert "bytes=8" in out.getvalue()      # counts summed by name
    assert events_summary.render_run([bad[0][1]], out=io.StringIO())


# ---- PR 24: the spans at their sites ---------------------------------

def _children(recs, parent_id):
    return [r for r in recs if r["parent"] == parent_id]


def test_graph_prep_leaves_relabel_and_layout_spans(capsys):
    from lux_tpu.graph import ShardedGraph, pair_relabel
    g = small_graph(nv=700, ne=9000)
    tip = _ring_tip()
    g2, _perm, starts = pair_relabel(g, 2, pair_threshold=4,
                                     verbose=True)
    ShardedGraph.build(g2, 2, starts=starts, pair_threshold=4)
    recs = _since(tip)
    names = [r["name"] for r in recs]
    assert names.count("layout.shard") == 1
    rel = next(r for r in recs if r["name"] == "relabel")
    kids = [r["name"] for r in _children(recs, rel["id"])]
    assert kids == ["relabel.degree_sort", "relabel.pair_histogram",
                    "relabel.deal", "relabel.rebuild_csc"]
    # -verbose prints one line per stage, from those records
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("# pair_relabel/")]
    assert [ln.split("/")[1].split(":")[0] for ln in lines] \
        == ["degree_sort", "pair_histogram", "deal", "rebuild_csc"]


@pytest.mark.parametrize("engine", ["push", "pull"])
def test_engine_build_leaves_every_child_once(engine):
    from lux_tpu.graph import ShardedGraph, pair_relabel
    g = small_graph(nv=700, ne=9000)
    g2, _perm, starts = pair_relabel(g, 1, pair_threshold=4)
    sg = ShardedGraph.build(g2, 1, starts=starts, pair_threshold=4)
    tip = _ring_tip()
    if engine == "push":
        eng = sssp.build_engine(g2, start_vertex=1, num_parts=1, sg=sg,
                                pair_threshold=4)
        want = ["build.pair_plan", "build.dense_layout",
                "build.sparse_view"]
    else:
        eng = pagerank.build_engine(g2, 1, None, sg=sg,
                                    pair_threshold=4)
        want = ["build.pair_plan", "build.dense_layout"]
    kids = [r for r in _since(tip) if r["name"].startswith("build.")]
    assert [r["name"] for r in kids] == want
    assert all(r["parent"] == 0 for r in kids)
    plan = kids[0]["counts"]
    assert plan["pair_edges"] + plan["residual_edges"] == g2.ne
    assert plan["pair_edges"] == eng.pairs.stats["covered"] > 0


def _names_and_bytes(recs):
    return [(r["name"], r["counts"]["bytes"]) for r in recs]


def test_state_spans_carry_the_bytes_they_move():
    """The whole ring of an ``init_state`` / ``place`` / ``unpad``, in
    the order the records close (children before their parent), and
    nothing else."""
    g = small_graph()
    eng = sssp.build_engine(g, start_vertex=1, num_parts=2)
    tip = _ring_tip()
    label, active = eng.init_state()
    nbytes = label.nbytes + active.nbytes
    label, active = eng.place(np.asarray(label), np.asarray(active))
    dist = eng.unpad(label)
    padded = np.asarray(label).nbytes
    assert _names_and_bytes(_own(_since(tip))) == [
        ("state.init.build", nbytes), ("state.init.put", nbytes),
        ("state.init", nbytes), ("state.place", nbytes),
        ("state.fetch.get", padded), ("state.fetch.unpad", dist.nbytes),
        ("state.fetch", padded)]
    assert dist.shape == (g.nv,)
    peng = pagerank.build_engine(g, 2, None)
    tip = _ring_tip()
    state = peng.init_state()
    rank = peng.unpad(peng.place(np.asarray(state)))
    assert _names_and_bytes(_own(_since(tip))) == [
        ("state.init.build", state.nbytes),
        ("state.init.put", state.nbytes),
        ("state.init", state.nbytes), ("state.place", state.nbytes),
        ("state.fetch.get", state.nbytes),
        ("state.fetch.unpad", rank.nbytes),
        ("state.fetch", state.nbytes)]


def _state_engine(engine, mesh_n, nv=40000, ne=160000):
    g = small_graph(nv=nv, ne=ne)
    parts = max(mesh_n, 1)
    mesh = make_mesh(mesh_n) if mesh_n else None
    if engine == "push":
        return g, sssp.build_engine(g, start_vertex=1, num_parts=parts,
                                    mesh=mesh)
    eng = pagerank.build_engine(g, parts, mesh)
    if engine == "pull-host":       # the same program without the hook
        eng = PullEngine(eng.sg, dataclasses.replace(
            eng.program, init_device=None), mesh=mesh)
    return g, eng


def _own(recs):
    """Without the compile marks of a first call (where an earlier
    test of this process installed ``runtime.watch_compiles``)."""
    return [r for r in recs if not r["name"].startswith("jit.")]


@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
@pytest.mark.parametrize("engine", ["push", "pull", "pull-host"])
def test_state_children_split_their_parent(engine, mesh_n):
    """PR 35: ``state.init`` = ``.build`` + ``.put``, ``state.fetch``
    = ``.get`` + ``.unpad``: the children carry the parent's id, do
    not overlap, cover it (90% in the best of a few repeats: the rest
    is the spans' own cost) and count the stated bytes; ``state.place``
    stays a leaf.  Where the devices make the state (PR 36: a pull
    program with ``init_device``) a warm ``state.init`` is tens of
    microseconds, of which three spans' own cost is a third: the floor
    there is 50%."""
    g, eng = _state_engine(engine, mesh_n)
    eng.init_state()            # the device path's one compile
    floor = {"state.init": 0.5 if engine == "pull" else 0.9,
             "state.fetch": 0.9}
    best = dict.fromkeys(floor, 0.0)
    for _ in range(5):
        tip = _ring_tip()
        state = eng.init_state()
        first = state[0] if engine == "push" else state
        nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
        answer = eng.unpad(first)
        recs = _own(_since(tip))
        for parent, kids, sizes in (
                ("state.init", ("build", "put"), (nbytes, nbytes)),
                ("state.fetch", ("get", "unpad"),
                 (first.nbytes, answer.nbytes))):
            (top,) = [r for r in recs if r["name"] == parent]
            got = _children(recs, top["id"])
            assert [k["name"] for k in got] \
                == [f"{parent}.{k}" for k in kids]
            assert [k["counts"]["bytes"] for k in got] == list(sizes)
            assert top["counts"]["bytes"] == sizes[0]
            if parent == "state.init" and engine != "push":
                assert top["counts"]["device_bytes"] \
                    == (nbytes if engine == "pull" else 0)
            a, b = got
            assert top["t0"] <= a["t0"] <= a["t1"] <= b["t0"] \
                <= b["t1"] <= top["t1"]
            covered = (a["t1"] - a["t0"] + b["t1"] - b["t0"]) \
                / (top["t1"] - top["t0"])
            best[parent] = max(best[parent], covered)
        assert answer.shape[0] == g.nv
        # nothing else was recorded, and no child has a child
        assert len(recs) == 6
    assert all(best[k] >= floor[k] for k in floor), best
    tip = _ring_tip()
    eng.place(*[np.asarray(x) for x in jax.tree.leaves(state)])
    assert [r["name"] for r in _own(_since(tip))] == ["state.place"]


# ---- PR 36: the pull engine's first state, made on the device --------

def _init_records(tip):
    (top,) = [r for r in _own(_since(tip)) if r["name"] == "state.init"]
    return top["counts"]


@pytest.mark.parametrize("pairs", [None, 4], ids=["dense", "pairs"])
@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
def test_device_init_is_the_host_init_bitwise(mesh_n, pairs):
    """``init_device`` and ``init`` are one formula: on the CPU backend
    the state the devices make equals ``program.init(sg)`` bit for
    bit, pad rows and vertices without out-edges included, and lies
    where ``shard_over_parts`` / ``jnp.asarray`` would have put it."""
    src, dst, nv = rmat_edges(scale=11, edge_factor=8, seed=5)
    g = Graph.from_edges(src, dst, nv + 37)     # so that pad rows exist
    parts = max(mesh_n, 1)
    mesh = make_mesh(mesh_n) if mesh_n else None
    eng = pagerank.build_engine(g, parts, mesh, pair_threshold=pairs)
    want = eng.program.init(eng.sg)
    assert want.dtype == np.float32
    deg = np.asarray(eng.sg.deg_padded)
    assert (deg == 0).any() and want.shape[1] * parts > g.nv
    tip = _ring_tip()
    state = eng.init_state()
    assert _init_records(tip) == {"bytes": want.nbytes,
                                  "device_bytes": want.nbytes}
    got = np.asarray(state)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    placed = eng.place(want)
    assert state.sharding.is_equivalent_to(placed.sharding, state.ndim)
    assert state.committed == placed.committed


@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
def test_run_takes_the_device_made_state_as_it_is(mesh_n):
    """A ``run`` from the device-made state compiles nothing that a
    ``run`` from a placed host state had not compiled, and a
    20-iteration solve from it is that solve bit for bit and meets
    the PageRank oracle."""
    from lux_tpu import runtime
    runtime.watch_compiles()
    g, eng = _state_engine("pull", mesh_n, nv=300, ne=2400)
    want = eng.run(eng.place(eng.program.init(eng.sg)), 20)
    eng.init_state()            # the init program's own compile
    tip = _ring_tip()
    got = eng.run(eng.init_state(), 20)
    assert [r["name"] for r in _since(tip)
            if r["name"].startswith("jit.")] == []
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        eng.unpad(got), pagerank.reference_pagerank(g, 20),
        rtol=2e-5, atol=1e-9)


def test_program_without_the_hook_takes_the_host_path():
    """colfilter has no ``init_device``: no device program is built,
    the state is its host init and ``device_bytes`` reads 0."""
    from lux_tpu.apps import colfilter
    eng = colfilter.build_engine(small_graph(weighted=True), 2)
    assert eng.program.init_device is None and eng._init_program is None
    tip = _ring_tip()
    state = eng.init_state()
    assert _init_records(tip) == {"bytes": state.nbytes,
                                  "device_bytes": 0}
    np.testing.assert_array_equal(np.asarray(state),
                                  eng.program.init(eng.sg))


def test_audit_stash_is_consumed_once_and_only_made_on_the_host_path():
    """The audit's stand-in for the state: a program with the hook
    takes shape and dtype from the device program and stashes nothing;
    one without it stashes its one host init, which the next
    ``init_state`` consumes (``device_bytes`` 0) and the one after
    that does not find."""
    _g, eng = _state_engine("pull", 0, nv=300, ne=2400)
    want = eng.program.init(eng.sg)
    sds = eng._audit_state_sds
    assert (sds.shape, sds.dtype) == (want.shape, want.dtype)
    assert eng._consume_pending_init() is None
    calls = []

    def counted(sg):
        calls.append(1)
        return want
    host = PullEngine(eng.sg, dataclasses.replace(
        eng.program, init=counted, init_device=None))
    assert host._audit_state_sds == sds and len(calls) == 1
    tip = _ring_tip()
    first = host.init_state()
    assert len(calls) == 1 and host._pending_init is None
    assert _init_records(tip)["device_bytes"] == 0
    second = host.init_state()
    assert len(calls) == 2
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
def test_eval_shape_and_checkpoint_resume_with_device_init(mesh_n,
                                                           tmp_path):
    """What resume reads of ``init_state``: ``jax.eval_shape`` of it
    gives the state's structure with nothing placed, and a
    checkpointed run cut short and resumed from that structure ends
    where the plain run ends."""
    from lux_tpu import checkpoint as ckpt
    _g, eng = _state_engine("pull", mesh_n, nv=300, ne=2400)
    want = eng.run(eng.init_state(), 8)
    shape = jax.eval_shape(eng.init_state)
    assert (shape.shape, shape.dtype) == (want.shape, want.dtype)
    path = str(tmp_path / "pr.npz")
    ckpt.run_checkpointed(eng, eng.init_state(), 5, path, segment=2)
    got = ckpt.run_checkpointed(eng, shape, 8, path, segment=2,
                                resume=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _segment_spans(recs):
    return [r for r in recs if r["name"].startswith("segment.")]


@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
def test_push_driver_spans_every_segment_once(mesh_n):
    """``segment.run`` (to the completion fence; ``iters`` = the
    fetched count the driver itself adds up) and ``segment.count``
    (the active count after it), once a segment, in this order."""
    from lux_tpu.segmented import each_converge_segment
    _g, eng = _state_engine("push", mesh_n, nv=600, ne=5000)
    log = telemetry.EventLog()
    tip = _ring_tip()
    totals = []
    with telemetry.use(events=log):
        for _label, _active, total in each_converge_segment(
                eng, *eng.init_state(), 2):
            totals.append(total)
            # suspended between two segments: no span is open
            assert telemetry._enclosing.get() == 0
    segs = _segment_spans(_since(tip))
    events = [e for e in log.events if e["kind"] == "segment"]
    assert len(totals) >= 3 and len(events) == len(totals)
    assert [r["name"] for r in segs] \
        == ["segment.run", "segment.count"] * len(totals)
    runs = segs[0::2]
    assert [r["counts"] for r in runs] \
        == [{"iters": e["iters"]} for e in events]
    assert np.cumsum([r["counts"]["iters"] for r in runs]).tolist() \
        == totals
    assert all(r["parent"] == 0 and not r["counts"] for r in segs[1::2])
    assert all(a["t1"] <= b["t0"] for a, b in zip(segs, segs[1:]))


@pytest.mark.parametrize("timed", [False, True], ids=["dispatch", "fenced"])
@pytest.mark.parametrize("mesh_n", [0, 4], ids=["np1", "mesh4"])
def test_pull_driver_spans_every_segment_once(mesh_n, timed):
    """``segment.run`` once a slice with ``iters`` = the slice's size,
    with an event sink (the driver fences) and without one (the span
    ends at dispatch); the pull driver counts nothing after it."""
    import contextlib

    from lux_tpu.segmented import each_run_segment
    _g, eng = _state_engine("pull", mesh_n, nv=600, ne=5000)
    log = telemetry.EventLog()
    tip = _ring_tip()
    with telemetry.use(events=log) if timed \
            else contextlib.nullcontext():
        for state in each_run_segment(eng, eng.init_state(), 7, 3):
            assert telemetry._enclosing.get() == 0
    jax.block_until_ready(state)
    segs = _segment_spans(_since(tip))
    assert [(r["name"], r["counts"]) for r in segs] == [
        ("segment.run", {"iters": n})
        for n in (3, 3, 1)]
    if timed:
        assert [e["n"] for e in log.events
                if e["kind"] == "segment"] == [3, 3, 1]


@pytest.mark.parametrize("program", ["push.step", "push.converge",
                                     "pull.step", "pull.run"])
def test_spans_do_not_enter_a_lowered_program(program):
    """A span is host bookkeeping: a program lowered under open spans
    is, byte for byte and with debug info, the program lowered under
    none (the check against the PARENT's text is PERF.md's, made off
    the chip from two checkouts)."""
    kind, name = program.split(".")
    _g, eng = _state_engine(kind, 0, nv=600, ne=5000)
    jitted, args = eng.audit_programs()[name]
    plain = jitted.lower(*args()).as_text(debug_info=True)
    with telemetry.span("state.init"), telemetry.span("segment.run"):
        spanned = jitted.lower(*args()).as_text(debug_info=True)
    assert spanned == plain
    assert "segment.run" not in plain and "state.init" not in plain


@pytest.mark.parametrize("variant", ["plain", "stats", "health",
                                     "delta"])
def test_sparse_iters_counts_the_branch_the_loop_took(variant):
    """``push.converge``'s ``sparse_iters`` against the count derived
    from ``converge_stats``' frontier series and the engine's own
    ``sparse_limit``, on a graph whose search takes BOTH branches; the
    answers are bitwise the all-dense engine's."""
    g = small_graph(nv=600, ne=5000, seed=3,
                    weighted=variant == "delta")
    kw = dict(start_vertex=1, num_parts=2, weighted=variant == "delta")
    if variant == "delta":
        kw["delta"] = 40
    eng = sssp.build_engine(g, health=variant == "health", **kw)
    usable, limit, _pull = eng._sparse_mode()
    assert usable
    _l, _a, it, fsz, *_ = eng.converge_stats(*eng.init_state())
    it = int(it)
    series = np.asarray(fsz)[:it].tolist()
    if variant == "delta":
        entering = series           # the bucket front entering a relax
    else:
        entering = [1] + series[:-1]
    want = sum(1 for c in entering if c <= limit)
    assert 0 < want < it, "the search must take both branches"
    tip = _ring_tip()
    if variant == "health":
        label, _a, it2, *_ = eng.converge_health(*eng.init_state())
    elif variant == "stats":
        label, _a, it2, *_ = eng.converge_stats(*eng.init_state())
    else:
        label, _a, it2 = eng.converge(*eng.init_state())
    # the mark is there before anything is fetched, scalars un-fetched
    raw = telemetry._RING[-1]
    assert raw["name"] == "push.converge"
    assert isinstance(raw["counts"]["sparse_iters"], jax.Array)
    marks = [r for r in _since(tip) if r["name"] == "push.converge"]
    assert len(marks) == 1
    counts = marks[0]["counts"]
    assert set(counts) == {"iters", "sparse_iters", "low_rung_iters",
                           "pull_iters", "queue_items", "queue_slots",
                           "budget_edges", "budget_slots", "advances",
                           "front_edges", "front_vertices",
                           "graph_edges", "edge_dense_iters"}
    # the bucket schedule's counts: the delta engine's alone
    if variant == "delta":
        assert type(counts["front_edges"]) is int
        assert type(counts["front_vertices"]) is int
        assert counts["front_edges"] > 0 and counts["advances"] >= 0
        assert counts["front_vertices"] == sum(entering)
        assert counts["graph_edges"] == g.ne
        assert 0 <= counts["edge_dense_iters"] <= it - want
    else:
        assert (counts["advances"], counts["front_edges"],
                counts["front_vertices"], counts["graph_edges"],
                counts["edge_dense_iters"]) == (0, 0, 0, 0, 0)
    # the four fill counts settle to plain ints (fr.Folded)
    assert all(type(counts[k]) is int for k in (
        "queue_items", "queue_slots", "budget_edges", "budget_slots"))
    assert 0 < counts["queue_items"] <= counts["queue_slots"]
    assert 0 < counts["budget_edges"] <= counts["budget_slots"]
    assert (counts["iters"], counts["sparse_iters"]) == (it, want)
    # which sparse iterations ran below the top edge budget has its
    # oracle in tests/test_push.py (the ladder)
    assert 0 <= counts["low_rung_iters"] <= want
    assert int(it2) == it
    dense = sssp.build_engine(g, enable_sparse=False, **kw)
    want_label, _a, _it = dense.converge(*dense.init_state())
    np.testing.assert_array_equal(np.asarray(label),
                                  np.asarray(want_label))
    dmark = telemetry.spans()[-1]
    assert dmark["counts"]["sparse_iters"] == 0


def test_jit_compile_record_on_a_first_call_and_none_on_a_second():
    from lux_tpu import runtime
    runtime.watch_compiles()
    runtime.watch_compiles()            # idempotent: one listener

    @jax.jit
    def fresh_program(x):
        return x * 3 + 1

    x = jax.numpy.ones(5)               # its own helper programs
    tip = _ring_tip()
    fresh_program(x)
    first = [r for r in _since(tip) if r["name"].startswith("jit.")]
    compiles = [r for r in first if r["name"] == "jit.compile"]
    assert len(compiles) == 1
    assert "fresh_program" in compiles[0]["counts"]["fun"]
    assert compiles[0]["t1"] > compiles[0]["t0"]
    assert {"jit.trace", "jit.lower"} <= {r["name"] for r in first}
    tip = _ring_tip()
    fresh_program(x)
    assert not [r for r in _since(tip) if r["name"].startswith("jit.")]


def test_threads_share_the_ring_and_one_log_without_losing_order(
        tmp_path):
    """More threads than cores span into one ring and one on-disk
    EventLog: no record is lost, ids are unique, each thread's nesting
    holds, and the file's monotonic ``tm`` never runs backwards (the
    log builds and writes an event under one lock; without it the
    write, which releases the interpreter lock, reorders lines)."""
    import os
    import sys
    import threading
    workers, each = 2 * (os.cpu_count() or 4), 150
    assert workers * each * 2 < telemetry.SPAN_RING
    tip = _ring_tip()
    path = str(tmp_path / "stress.jsonl")
    log = telemetry.EventLog(path)
    errors = []

    def work(w):
        try:
            with telemetry.use(events=log):
                for i in range(each):
                    with telemetry.span("stress.outer", w=w, i=i) as o:
                        with telemetry.span("stress.inner", w=w) as k:
                            pass
                        assert k.parent == o.id and o.parent == 0
        except Exception as e:      # noqa: BLE001 - reported below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    recs = [r for r in _since(tip) if r["name"].startswith("stress.")]
    assert len(recs) == workers * each * 2
    assert len({r["id"] for r in recs}) == len(recs)
    outer = {r["id"]: r for r in recs if r["name"] == "stress.outer"}
    for r in recs:
        if r["name"] == "stress.inner":
            assert outer[r["parent"]]["counts"]["w"] == r["counts"]["w"]
    evs = [e for e in log.events if e["kind"] == "span"]
    assert len(evs) == len(recs)
    log.close()
    with open(path) as f:
        tms = [json.loads(line)["tm"] for line in f]
    assert len(tms) == len(recs) and tms == sorted(tms)
