"""Paged two-level gather (ops/pagegather.py): plan-resolution oracle
(every edge's (page, slot, lane) decodes back to its original index;
padding hits the identity), device-vs-oracle agreement, paged-vs-flat
engine equivalence for all four apps on 1 and 8 virtual devices
(stats/health variants and a batched config included), the scalemodel
break-even pin, the ledger pricing, and the observe phase model.

Bitwise discipline: min/max reductions (sssp, cc) are order-
independent, so paged-vs-flat is ``array_equal`` outright.  Sum
reductions re-associate between layouts by construction, so the exact
proof runs on sub-2^24 integer-valued states where f32 sums are exact
in ANY order — the repo's established trick
(ops/pairs.stacked_pair_dot_numpy); the real pagerank/colfilter apps
are additionally held to tight allclose.
"""

import numpy as np
import pytest

from lux_tpu.graph import Graph, ShardedGraph
from lux_tpu.ops.pagegather import (W, decode_plan, paged_dot_numpy,
                                    paged_reduce_numpy,
                                    plan_owner_paged, plan_paged_gather,
                                    resolve_gather)


def _skewed_graph(seed, nv, ne, weighted=False):
    rng = np.random.default_rng(seed)
    src = (rng.zipf(1.3, ne) - 1) % nv
    dst = (rng.zipf(1.2, ne) - 1) % nv
    w = rng.integers(1, 6, ne).astype(np.float32) if weighted else None
    return Graph.from_edges(src.astype(np.uint32),
                            dst.astype(np.uint32), nv, weights=w)


def full_oracle(src_slot, dst_local, state, vpad):
    out = np.zeros(vpad)
    for s, d in zip(src_slot, dst_local):
        out[d] += state[s]
    return out


# ---------------------------------------------------------------------
# plan builder oracle


@pytest.mark.parametrize("num_parts", [1, 3])
def test_plan_resolves_every_edge(num_parts):
    """Every edge's (page, slot, lane) decodes back to its original
    (src, dst) index — multiset equality per part — and dead lanes
    (rel == -1) are exactly the padding."""
    g = _skewed_graph(3, 4 * W, 7000)
    sg = ShardedGraph.build(g, num_parts, vpad_align=128)
    pp = plan_paged_gather(sg)
    assert pp.stats["ne"] == g.ne
    for p in range(num_parts):
        nep = int(sg.ne_part[p])
        src, dst = decode_plan(pp, p)
        assert len(src) == nep          # total coverage, no drops
        want = sorted(zip(sg.src_slot[p, :nep].tolist(),
                          sg.dst_local[p, :nep].tolist()))
        got = sorted(zip(src.tolist(), dst.tolist()))
        assert got == want
        # every live lane's page slot is in range of the dedup list
        sl = pp.slot_lane[p]
        live = pp.rel_dst[p] != -1
        slots = (sl[:, 0] >> np.uint32(7)).astype(np.int64)
        used = slots[live.any(axis=1)]
        assert used.size == 0 or used.max() < pp.n_pages


def test_plan_padding_hits_identity():
    """Dead lanes and dead rows contribute the reduce identity: the
    oracle partial over a plan equals the full flat reduce."""
    g = _skewed_graph(5, 3 * W, 5000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_paged_gather(sg)
    state = np.random.default_rng(0).random(sg.num_parts * sg.vpad)
    for p in range(sg.num_parts):
        nep = int(sg.ne_part[p])
        want = full_oracle(sg.src_slot[p, :nep],
                           sg.dst_local[p, :nep], state, sg.vpad)
        got = paged_reduce_numpy(pp, p, state)[:sg.vpad]
        np.testing.assert_allclose(got, want, rtol=1e-9)


def test_plan_stats_recorded():
    from lux_tpu.ops.pagegather import plan_owner_paged, plan_paged_stats

    g = _skewed_graph(7, 4 * W, 9000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_paged_gather(sg)
    st = pp.stats
    assert st["rows"] >= 1 and st["fill"] == pytest.approx(
        st["ne"] / st["rows"])
    assert st["page_ratio"] == pytest.approx(
        st["unique_pages"] * W / st["ne"])
    # the padded leading dims never collide with the reshaped state
    # table's row count (the audit operand-shape disambiguation)
    n_src_rows = sg.num_parts * sg.vpad // W
    assert pp.Rp != n_src_rows and pp.n_pages != n_src_rows
    # the counting-only fast path (what gather="auto" resolves from
    # without materializing plan arrays) must agree with the full
    # build EXACTLY, dense and owner
    assert plan_paged_stats(sg) == st
    assert plan_paged_stats(sg, exchange="owner") \
        == plan_owner_paged(sg).stats


def test_owner_plan_resolves_every_edge():
    """Owner plan: per SOURCE part, pages within the own shard and
    GLOBAL destination tiles — decoded edges must partition the whole
    edge set by source part."""
    g = _skewed_graph(11, 4 * W, 6000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_owner_paged(sg)
    assert pp.n_tiles == sg.num_parts * sg.vpad // W
    want_all = []
    for r in range(sg.num_parts):
        nep = int(sg.ne_part[r])
        slot = sg.src_slot[r, :nep].astype(np.int64)
        dst = sg.dst_local[r, :nep].astype(np.int64)
        s = slot // sg.vpad
        gdst = r * sg.vpad + dst        # global tile*W + rel encoding
        want_all += list(zip(s.tolist(), (slot - s * sg.vpad).tolist(),
                             gdst.tolist()))
    got_all = []
    for p in range(sg.num_parts):
        src, dst = decode_plan(pp, p)
        got_all += [(p, int(a), int(b)) for a, b in zip(src, dst)]
    assert sorted(got_all) == sorted(want_all)


# ---------------------------------------------------------------------
# scalemodel break-even pin (the round-15 recorded threshold)


def test_page_break_even_pinned():
    from lux_tpu import scalemodel as sm
    # modeled row cost: measured pair-row machinery + the 128-lane
    # shuffle
    assert sm.PAGED_ROW_NS == pytest.approx(150.0 + 128 * 0.38)
    # small-table scalar break-even at page_ratio 1: fill >= 23
    assert sm.page_break_even_fill() == 23
    # past the big-table cliff the flat rate is worse, so the paged
    # path pays at lower fill
    assert sm.page_break_even_fill(table_bytes=200e6) == 14
    # a page ratio so high the dedup'd fetch alone exceeds the flat
    # rate can never win
    assert sm.page_break_even_fill(page_ratio=100.0) >= 1 << 30
    # threshold in the other direction: the unique-page ratio below
    # which full rows beat the flat gather
    r = sm.page_break_even_ratio(128.0)
    assert r == pytest.approx(
        (sm.GATHER_SMALL_NS - sm.PAGED_ROW_NS / 128.0)
        / (sm.PAGE_ROW_FETCH_NS / 128.0))
    assert sm.page_gather_ns(1.0, 128.0) < sm.GATHER_SMALL_NS
    assert sm.page_gather_ns(1.0, 4.0) > sm.GATHER_SMALL_NS


def test_resolve_gather_auto():
    from lux_tpu import scalemodel as sm

    dense = dict(page_ratio=0.5, fill=100.0)
    sparse = dict(page_ratio=3.0, fill=2.0)
    assert resolve_gather("auto", dense, 1 << 20) == "paged"
    assert resolve_gather("auto", sparse, 1 << 20) == "flat"
    assert resolve_gather("paged", sparse, 1 << 20) == "paged"
    assert resolve_gather("flat", dense, 1 << 20) == "flat"
    with pytest.raises(ValueError, match="gather"):
        resolve_gather("bogus", dense, 1)
    # owner engines compare against the owner scan rate (~11.9
    # ns/slot), NOT the big-table flat cliff (14.6): a plan whose
    # modeled cost lands between the two must stay flat on an owner
    # engine (it would regress vs the scan) while beating the flat
    # gather past the cliff
    fill_mid = dict(page_ratio=0.1, fill=15.0, padded_fill=15.0)
    mid = sm.page_gather_ns(0.1, 15.0)
    assert sm.OWNER_SLOT_NS * 1.2 < mid < sm.GATHER_BIG_NS
    big = int(200e6)
    assert resolve_gather("auto", fill_mid, big) == "paged"
    assert resolve_gather("auto", fill_mid, big,
                          exchange="owner") == "flat"


# ---------------------------------------------------------------------
# engine equivalence: paged vs flat, all four apps


def _converge(eng):
    label, active = eng.init_state()
    label, _a, _it = eng.converge(label, active)
    return eng.unpad(label)


def test_sssp_cc_paged_bitwise_single_and_mesh():
    """min/max reductions are order-independent: paged and flat runs
    are ``array_equal`` outright, on one device AND the 8-virtual-
    device mesh — the acceptance equivalence for the push apps."""
    from lux_tpu.apps import components, sssp
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.parallel.mesh import make_mesh

    g = _skewed_graph(7, 3 * W, 4000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    flat = _converge(PushEngine(sg, sssp.make_program(0)))
    paged = _converge(PushEngine(sg, sssp.make_program(0),
                                 gather="paged"))
    assert np.array_equal(flat, paged)
    assert np.array_equal(
        paged, sssp.reference_sssp(g, 0).astype(paged.dtype))

    s2, d2 = components.symmetrize(*g.edge_arrays())
    gc = Graph.from_edges(s2.astype(np.uint32), d2.astype(np.uint32),
                          g.nv)
    sgc = ShardedGraph.build(gc, 2, vpad_align=128)
    cf = _converge(PushEngine(sgc, components.make_program()))
    cp = _converge(PushEngine(sgc, components.make_program(),
                              gather="paged"))
    assert np.array_equal(cf, cp)

    mesh = make_mesh(8)
    sg8 = ShardedGraph.build(g, 8, vpad_align=128)
    mp = _converge(PushEngine(sg8, sssp.make_program(0), mesh=mesh,
                              gather="paged"))
    assert np.array_equal(mp, flat)


def test_sum_paged_exact_on_integer_states():
    """f32 sums re-associate between the paged and flat layouts by
    construction, so the exact proof runs on sub-2^24 integer-valued
    states where f32 addition is exact in ANY order (the repo's
    established trick, ops/pairs.stacked_pair_dot_numpy) — paged and
    flat sum engines are then ``array_equal``, single device and
    8-device mesh."""
    from lux_tpu.engine.program import PullProgram
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.parallel.mesh import make_mesh

    g = _skewed_graph(9, 3 * W, 4000)
    vals = np.random.default_rng(0).integers(0, 8, g.nv).astype(
        np.float32)

    def mk():
        return PullProgram(
            reduce="sum",
            edge_value=lambda s, d, w: s,
            apply=lambda o, r, c: r,
            init=lambda sg: sg.to_padded(vals))

    sg = ShardedGraph.build(g, 2, vpad_align=128)
    flat = PullEngine(sg, mk())
    paged = PullEngine(sg, mk(), gather="paged")
    a = flat.unpad(flat.step(flat.init_state()))
    b = paged.unpad(paged.step(paged.init_state()))
    assert np.array_equal(a, b)

    mesh = make_mesh(8)
    sg8 = ShardedGraph.build(g, 8, vpad_align=128)
    pm = PullEngine(sg8, mk(), mesh=mesh, gather="paged")
    c = pm.unpad(pm.step(pm.init_state()))
    assert np.array_equal(a, c)


def test_pagerank_colfilter_paged_vs_flat():
    """The real sum apps at tight tolerance (their f32 sum order
    differs between layouts; the exact proof is the integer-state
    test above), plus the colfilter SDDMM dot path."""
    from lux_tpu.apps import colfilter, pagerank
    from lux_tpu.engine.pull import PullEngine

    g = _skewed_graph(11, 3 * W, 4000, weighted=True)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pf = PullEngine(sg, pagerank.make_program())
    pp_ = PullEngine(sg, pagerank.make_program(), gather="paged")
    a = pf.unpad(pf.run(pf.init_state(), 6))
    b = pp_.unpad(pp_.run(pp_.init_state(), 6))
    np.testing.assert_allclose(b, a, rtol=1e-6)

    cf = PullEngine(sg, colfilter.make_program())
    cp = PullEngine(sg, colfilter.make_program(), gather="paged")
    x = cf.unpad(cf.run(cf.init_state(), 3))
    y = cp.unpad(cp.run(cp.init_state(), 3))
    np.testing.assert_allclose(y, x, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(
        y, colfilter.reference_colfilter(g, 3), rtol=1e-4, atol=1e-7)


def test_colfilter_paged_dot_exact_oracle():
    """Integer states/weights under 2^24: the paged SDDMM delivery is
    BITWISE equal to its float64 oracle (order-independent exactness;
    the dot-path acceptance proof)."""
    g = _skewed_graph(13, 2 * W, 2000, weighted=True)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_paged_gather(sg)
    rng = np.random.default_rng(0)
    K = 4
    state = rng.integers(0, 4, (sg.num_parts * sg.vpad, K)).astype(
        np.float32)
    import jax.numpy as jnp

    from lux_tpu.ops.pagegather import paged_partial_dot

    def msg(S, dot, wt):
        return (wt - dot)[..., None] * S

    for p in range(sg.num_parts):
        t0 = p * (sg.vpad // W)
        got = np.asarray(paged_partial_dot(
            pp, jnp.asarray(state), jnp.asarray(pp.page_ids[p]),
            jnp.asarray(pp.slot_lane[p]), jnp.asarray(pp.rel_dst[p]),
            jnp.asarray(pp.weight[p]), jnp.asarray(pp.row_tile[p]),
            jnp.asarray(pp.tile_pos[p]), t0, msg))
        want = paged_dot_numpy(pp, p, state, t0, msg)
        assert np.array_equal(got, want)


def test_owner_paged_matches_flat():
    """exchange='owner' + gather='paged': the generation scan runs
    the page-binned shard delivery — same fixed point as the flat
    owner AND the flat gather engines (min = bitwise)."""
    from lux_tpu.apps import pagerank, sssp
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.engine.push import PushEngine

    g = _skewed_graph(17, 3 * W, 4000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    flat = _converge(PushEngine(sg, sssp.make_program(0)))
    op = _converge(PushEngine(sg, sssp.make_program(0),
                              exchange="owner", gather="paged"))
    assert np.array_equal(flat, op)

    pf = PullEngine(sg, pagerank.make_program())
    po = PullEngine(sg, pagerank.make_program(), exchange="owner",
                    gather="paged")
    assert po.page_plan is not None and po.owner is None
    a = pf.unpad(pf.run(pf.init_state(), 5))
    b = po.unpad(po.run(po.init_state(), 5))
    np.testing.assert_allclose(b, a, rtol=1e-6)


def test_batched_paged_bitwise():
    """One batched (B > 1) config: k-source SSSP columns are bitwise
    identical between the paged and flat dense iterations (min
    reduce), and personalized PageRank stays within float tolerance."""
    from lux_tpu.apps import pagerank, sssp
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.engine.push import PushEngine

    g = _skewed_graph(19, 3 * W, 4000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    srcs = [0, 5, 11]
    bf = _converge(PushEngine(sg, sssp.make_batched_program(srcs)))
    bp = _converge(PushEngine(sg, sssp.make_batched_program(srcs),
                              gather="paged"))
    assert np.array_equal(bf, bp)

    resets = pagerank.one_hot_resets(g.nv, srcs)
    ef = PullEngine(sg, pagerank.make_batched_program(resets))
    ep = PullEngine(sg, pagerank.make_batched_program(resets),
                    gather="paged")
    a = ef.unpad(ef.run(ef.init_state(), 4))
    b = ep.unpad(ep.run(ep.init_state(), 4))
    # B=3 engages the auto MXU sum on both engines (round 23); the
    # paged and flat layouts contract lanes in different orders, so
    # float sums agree to tolerance, not bitwise (PERF_NOTES r23).
    np.testing.assert_allclose(b, a, rtol=5e-6)


def test_paged_stats_and_health_variants():
    """The counter/watchdog loop variants run the SAME paged core:
    states bitwise-equal to the plain run, counters well-formed,
    watchdog clean (the stats/health acceptance slice)."""
    import jax

    from lux_tpu import health as hw
    from lux_tpu.apps import pagerank, sssp
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.engine.push import PushEngine

    g = _skewed_graph(23, 3 * W, 4000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)

    eng = PullEngine(sg, pagerank.make_program(), gather="paged")
    plain = eng.run(eng.init_state(), 4)
    s2, res, chg, resp, chgp = eng.run_stats(eng.init_state(), 4)
    assert np.array_equal(np.asarray(plain), np.asarray(s2))
    assert np.asarray(res)[:4].min() > 0
    s3, _it, rb, cb, rbp, cbp, watch = eng.run_health(
        eng.init_state(), 4)
    hw.ensure_ok(watch, engine="pull", where="paged stats test")
    assert np.array_equal(np.asarray(plain), np.asarray(s3))

    pe = PushEngine(sg, sssp.make_program(0), gather="paged",
                    health=True)
    l0, a0 = pe.init_state()
    l1, a1, it, fsz, fed, fszp, fedp, pwatch = pe.converge_health(
        l0, a0)
    hw.ensure_ok(pwatch, engine="push", where="paged push health")
    flat = _converge(PushEngine(sg, sssp.make_program(0)))
    assert np.array_equal(pe.unpad(l1), flat)
    it = int(jax.device_get(it))
    # scalar edge counters sum the per-part rows bitwise
    assert np.array_equal(np.asarray(fed)[:it],
                          np.asarray(fedp)[:it].sum(axis=1,
                                                    dtype=np.uint32))


def test_paged_rejects_bad_configs():
    from lux_tpu.apps import pagerank
    from lux_tpu.engine.pull import PullEngine

    g = _skewed_graph(29, 3 * W, 3000)
    sg8 = ShardedGraph.build(g, 2)            # vpad_align 8: unaligned
    with pytest.raises(ValueError, match="vpad"):
        PullEngine(sg8, pagerank.make_program(), gather="paged")
    # auto on an unaligned build silently stays flat
    eng = PullEngine(sg8, pagerank.make_program(), gather="auto")
    assert eng.page_plan is None and eng.gather == "flat"
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    with pytest.raises(ValueError, match="pair"):
        PullEngine(sg, pagerank.make_program(), gather="paged",
                   pair_threshold=4)


# ---------------------------------------------------------------------
# ledger + observe + check_bench integration


def test_memory_report_prices_paged_plan():
    g = _skewed_graph(31, 3 * W, 4000, weighted=True)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_paged_gather(sg)
    base = sg.memory_report()
    rep = sg.memory_report(page_plan=pp)
    want_edges = (pp.slot_lane.nbytes + pp.rel_dst.nbytes
                  + pp.row_tile.nbytes + pp.tile_pos.nbytes
                  + pp.page_ids.nbytes + pp.weight.nbytes) // 2
    assert rep["edge_bytes_per_part"] == want_edges
    assert rep["page_buffer_bytes_per_part"] == pp.n_pages * 128 * 4
    # the delivered-rows temporaries (vals + row partials, the same
    # 2x-Rp term the pair path prices) must be in the advisor total:
    # an unpriced paged build would pass the advisor and OOM on its
    # first iteration (the pair path's measured RMAT25 failure mode)
    assert rep["page_temp_bytes_per_part"] == 2 * pp.Rp * 128 * 4
    assert rep["total_bytes"] != base["total_bytes"]


def test_engine_ledger_check_paged():
    """check_ledger on a paged engine: the priced plan arrays + page
    buffer stay within tolerance of the compiled step's argument
    bytes (the audit matrix's paged ledger config, asserted
    directly)."""
    from lux_tpu import audit
    from lux_tpu.apps import pagerank

    rng = np.random.default_rng(0)
    g = Graph.from_edges(rng.integers(0, 2048, 32768),
                         rng.integers(0, 2048, 32768), 2048)
    eng = pagerank.build_engine(g, num_parts=2, gather="paged")
    assert eng.page_plan is not None
    findings = audit.check_ledger(eng)
    assert [f for f in findings if f.severity == "error"] == []


def test_check_bench_gather_fields(tmp_path):
    import subprocess
    import sys
    from pathlib import Path

    REPO = Path(__file__).resolve().parent.parent
    good = {"metric": "pagerank_paged_rmat21_gteps_per_chip",
            "value": 0.5, "unit": "GTEPS", "vs_baseline": 0.5,
            "samples": [0.5], "attempts": 1, "discarded": [],
            "gather": "paged", "page_ratio": 0.02, "page_fill": 97.3,
            "telemetry": {"runs": [{"repeat": 0, "iters": 20,
                                    "seconds": 1.0}],
                          "counters": None},
            "calibration": {
                "session": "s", "platform": "tpu", "backend": "tpu",
                "ndev": 1, "grade": "canonical", "deviation": 1.0,
                "probe": {"gather_small_ns": 9.0},
                "audit": {"errors": 0, "warnings": 0}}}
    import copy
    import json
    bad1 = copy.deepcopy(good)
    del bad1["page_ratio"]
    bad2 = copy.deepcopy(good)
    bad2["gather"] = "flat"    # contradicts the metric name
    bad3 = copy.deepcopy(good)
    bad3["page_fill"] = 600.0

    p = tmp_path / "lines.jsonl"
    p.write_text("\n".join(json.dumps(x) for x in [good]))
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_bench.py"),
         str(p)], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    p.write_text("\n".join(json.dumps(x)
                           for x in [bad1, bad2, bad3]))
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_bench.py"),
         str(p)], capture_output=True, text=True)
    assert r.returncode == 1
    assert "page_ratio" in r.stderr
    assert "contradicts the metric name" in r.stderr
    assert "page_fill" in r.stderr


def test_lint_gates_bench_fencing(tmp_path):
    """The bench-fence check: block_until_ready in a scripts/ file is
    a finding; the pragma suppresses it; lux_tpu files are exempt
    (the engines legitimately never use it anyway)."""
    import subprocess
    import sys
    from pathlib import Path

    REPO = Path(__file__).resolve().parent.parent
    sdir = tmp_path / "scripts"
    sdir.mkdir()
    bad = sdir / "profile_thing.py"
    bad.write_text("import jax\n"
                   "out = 1\n"
                   "jax.block_until_ready(out)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True)
    assert r.returncode == 1 and "bench-fence" in r.stderr

    ok = sdir / "profile_ok.py"
    ok.write_text("import jax\n"
                  "out = 1\n"
                  "# one-off interactive poke, not a timed region\n"
                  "# audit: allow(bench-fence)\n"
                  "jax.block_until_ready(out)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(ok)], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    # the repo's own scripts tree is clean under the gate (the
    # rounds-12/15 loop_bench port)
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(REPO / "scripts")], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------
# page-major layout (round 16): gather rows bind to pages first


def test_pagemajor_plan_resolves_every_edge():
    """Every edge decodes back through its virtual row's gather row —
    multiset equality per part — and the gather rows are near-full by
    construction (that is the mode's whole point)."""
    from lux_tpu.ops.pagegather import plan_pagemajor

    g = _skewed_graph(11, 4 * W, 7000)
    for P in (1, 3):
        sg = ShardedGraph.build(g, P, vpad_align=128)
        pp = plan_pagemajor(sg)
        assert pp.mode == "pagemajor"
        assert pp.stats["g_fill"] > pp.stats["fill"]
        for p in range(P):
            nep = int(sg.ne_part[p])
            src, dst = decode_plan(pp, p)
            assert len(src) == nep
            want = sorted(zip(sg.src_slot[p, :nep].tolist(),
                              sg.dst_local[p, :nep].tolist()))
            assert sorted(zip(src.tolist(), dst.tolist())) == want


def test_pagemajor_oracle_reduce_matches_flat():
    """paged_reduce_numpy through the virtual-row indirection equals
    the plain flat reduce (padding contributes the identity)."""
    from lux_tpu.ops.pagegather import plan_pagemajor

    g = _skewed_graph(12, 3 * W, 5000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    pp = plan_pagemajor(sg)
    state = np.random.default_rng(1).random(sg.num_parts * sg.vpad)
    for p in range(2):
        nep = int(sg.ne_part[p])
        out = paged_reduce_numpy(pp, p, state, "sum")
        ref = np.zeros(sg.vpad)
        np.add.at(ref, sg.dst_local[p, :nep],
                  state[sg.src_slot[p, :nep]])
        assert np.allclose(out[:sg.vpad], ref)


def test_pagemajor_owner_plan_decodes():
    """The owner page-major plan's routed layout decodes back to the
    full edge multiset: every (src part, src local, global dst)
    appears exactly once across the destination parts' receive
    plans."""
    from lux_tpu.ops.pagegather import (decode_pagemajor_owner,
                                        plan_owner_pagemajor)

    g = _skewed_graph(13, 4 * W, 6000)
    P = 4
    sg = ShardedGraph.build(g, P, vpad_align=128)
    po = plan_owner_pagemajor(sg)
    assert po.route >= 8 and po.route % 8 == 0
    got = []
    for d in range(P):
        s, srcl, dstl = decode_pagemajor_owner(po, d)
        got += list(zip(s.tolist(), srcl.tolist(),
                        (d * sg.vpad + dstl).tolist()))
    want = []
    for r in range(P):
        nep = int(sg.ne_part[r])
        slot = sg.src_slot[r, :nep].astype(np.int64)
        sp = slot // sg.vpad
        want += list(zip(sp.tolist(),
                         (slot - sp * sg.vpad).tolist(),
                         (r * sg.vpad
                          + sg.dst_local[r, :nep]).tolist()))
    assert sorted(got) == sorted(want)


def test_pagemajor_engines_match_flat():
    """gather='pagemajor' engines reproduce the flat engines: bitwise
    for the min/max push apps (order-independent), on one device, the
    8-device mesh, the OWNER routing exchange, and a batched build;
    integer-exact for a sum pull step."""
    from lux_tpu.apps import sssp
    from lux_tpu.engine.program import PullProgram
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.parallel.mesh import make_mesh

    g = _skewed_graph(14, 4 * W, 6000)
    sg = ShardedGraph.build(g, 2, vpad_align=128)
    flat = _converge(PushEngine(sg, sssp.make_program(0)))
    pm = _converge(PushEngine(sg, sssp.make_program(0),
                              gather="pagemajor"))
    assert np.array_equal(flat, pm)
    pmo = _converge(PushEngine(sg, sssp.make_program(0),
                               exchange="owner",
                               gather="pagemajor"))
    assert np.array_equal(flat, pmo)

    mesh = make_mesh(8)
    sg8 = ShardedGraph.build(g, 8, vpad_align=128)
    pm8 = _converge(PushEngine(sg8, sssp.make_program(0), mesh=mesh,
                               gather="pagemajor"))
    assert np.array_equal(flat, pm8)
    pm8o = _converge(PushEngine(sg8, sssp.make_program(0), mesh=mesh,
                                exchange="owner",
                                gather="pagemajor"))
    assert np.array_equal(flat, pm8o)

    # batched (k-source) labels ride the trailing query axis
    ks_flat = _converge(PushEngine(sg, sssp.make_batched_program(
        [0, 5, 9])))
    ks_pm = _converge(PushEngine(sg, sssp.make_batched_program(
        [0, 5, 9]), gather="pagemajor"))
    assert np.array_equal(ks_flat, ks_pm)

    # integer-exact sum pull step (the established f32-exactness
    # trick): flat vs pagemajor vs pagemajor+owner
    vals = np.random.default_rng(2).integers(0, 8, g.nv).astype(
        np.float32)

    def mk():
        return PullProgram(
            reduce="sum",
            edge_value=lambda s, d, w: s,
            apply=lambda o, r, c: r,
            init=lambda sgx: sgx.to_padded(vals))

    a = PullEngine(sg, mk())
    b = PullEngine(sg, mk(), gather="pagemajor")
    c = PullEngine(sg, mk(), gather="pagemajor", exchange="owner")
    ra = a.unpad(a.step(a.init_state()))
    assert np.array_equal(ra, b.unpad(b.step(b.init_state())))
    assert np.array_equal(ra, c.unpad(c.step(c.init_state())))


def test_pagemajor_break_even_pinned():
    from lux_tpu import scalemodel as sm

    # the 150 ns pair-row machinery splits: 24 ns static row fetch +
    # the compare-reduce/combine remainder
    assert sm.VROW_REDUCE_NS == pytest.approx(150.0 - 24.0)
    # full gather rows pay fetch+shuffle once; the virtual-row
    # break-even undercuts the plain paged 23
    assert sm.pagemajor_break_even_vfill() == 19
    assert sm.pagemajor_break_even_vfill() < sm.page_break_even_fill()
    # the routing hop is ~0.1 ns/edge at full rows — priced, small
    assert 0.0 < sm.pagemajor_route_ns(128.0) < 0.2
    assert sm.pagemajor_break_even_vfill(routed=True) >= \
        sm.pagemajor_break_even_vfill()
    with pytest.raises(ValueError, match="K-dim"):
        sm.pagemajor_gather_ns(1.0, 128.0, 30.0, kdim=20)


def test_resolve_gather_three_way():
    """auto arbitration with the pm counting present: page-major wins
    exactly when its modeled split rate undercuts both flat and
    paged; without pm keys the old two-way behavior is unchanged."""
    from lux_tpu import scalemodel as sm

    # virtual fill below the paged break-even but above the
    # page-major one, gather rows full -> pagemajor
    st = dict(page_ratio=0.3, fill=20.0, padded_fill=20.0,
              pm_padded_vfill=20.0, pm_g_padded_fill=120.0)
    assert sm.pagemajor_gather_ns(0.3, 120.0, 20.0) \
        < sm.GATHER_SMALL_NS < sm.page_gather_ns(0.3, 20.0)
    assert resolve_gather("auto", st, 1 << 20) == "pagemajor"
    # high fill: paged's single-level pipeline models cheaper than
    # pm's extra virtual take whenever vfill ~ gfill
    dense = dict(page_ratio=0.3, fill=120.0, padded_fill=120.0,
                 pm_padded_vfill=120.0, pm_g_padded_fill=120.0)
    assert resolve_gather("auto", dense, 1 << 20) == "paged"
    # hopeless fills stay flat even with pm keys
    sparse = dict(page_ratio=3.0, fill=2.0, padded_fill=2.0,
                  pm_padded_vfill=2.0, pm_g_padded_fill=10.0)
    assert resolve_gather("auto", sparse, 1 << 20) == "flat"
    assert resolve_gather("pagemajor", sparse, 1 << 20) == "pagemajor"


def test_pagemajor_guards():
    """Typed refusals: K-dim (SDDMM) programs cannot take
    gather='pagemajor'; pair_threshold conflicts like paged."""
    from lux_tpu.apps import colfilter, pagerank

    gw = _skewed_graph(15, 3 * W, 4000, weighted=True)
    with pytest.raises(ValueError, match="K-dim|SDDMM"):
        colfilter.build_engine(gw, num_parts=1, gather="pagemajor")
    g = _skewed_graph(15, 3 * W, 4000)
    with pytest.raises(ValueError, match="pair"):
        pagerank.build_engine(g, num_parts=1, gather="pagemajor",
                              pair_threshold=8)


def test_pagemajor_ledger_prices_clean():
    """memory_report(page_plan=pm plan) prices the plan arrays + the
    gather-row buffer; the audit ledger check stays clean on a dense
    pagemajor build."""
    from lux_tpu import audit
    from lux_tpu.apps import pagerank

    r = np.random.default_rng(4)
    g = Graph.from_edges(r.integers(0, 2048, 32768),
                         r.integers(0, 2048, 32768), 2048)
    eng = pagerank.build_engine(g, num_parts=2, gather="pagemajor")
    assert eng.gather == "pagemajor"
    rep = eng.sg.memory_report(page_plan=eng.page_plan)
    assert rep["page_temp_bytes_per_part"] > 0
    assert rep["edge_bytes_per_part"] > 0
    findings = audit.audit_engine(eng, mode=None, ledger=True)
    assert not [f for f in findings if f.severity == "error"], \
        [str(f) for f in findings]
