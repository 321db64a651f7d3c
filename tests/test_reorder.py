"""Locality harvest (round 16): page-aware vertex reordering.

- native/numpy reorder contract: bijection, degree histogram
  preserved, every mode;
- the hill-climb driver's measured-objective trail and the ROADMAP
  acceptance: on the scrambled locality-rich community shape the
  measured ``page_fill`` rises from the R-MAT 6-12 band to >= 23
  (the paged break-even) and ``gather="auto"`` leaves the flat path;
- permutation-invariance oracles: each of the four apps runs on a
  reordered graph, results map back through the inverse permutation
  and must equal the unreordered run — BITWISE for the integer
  (min/max) apps, tolerance for the float (sum) apps whose reductions
  re-associate — on 1 and 8 virtual devices;
- the ``.perm`` sidecar round-trip through ``Graph.from_file``;
- the bench gather-ab reorder lines end-to-end through
  scripts/check_bench.py.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from lux_tpu import format as luxfmt
from lux_tpu import native
from lux_tpu.convert import community_graph
from lux_tpu.graph import Graph
from lux_tpu.reorder import apply_perm, page_fill_stats, page_reorder

REPO = Path(__file__).resolve().parent.parent


def _community(scale=12, ef=8, cs=7, seed=0, weighted=False):
    return community_graph(scale=scale, edge_factor=ef,
                           community_scale=cs, seed=seed,
                           weighted=weighted)


# ---------------------------------------------------------------------
# reorder pass contract


@pytest.mark.parametrize("mode", ["cm", "hubs", "communities"])
def test_reorder_cluster_bijection_and_degrees(mode):
    g = _community(scale=10, ef=6)
    src, dst = g.edge_arrays()
    perm = native.reorder_cluster(src, dst, g.nv, mode=mode)
    assert sorted(perm.tolist()) == list(range(g.nv))
    # degree histogram preserved under the relabel: deg_new[i] ==
    # deg_old[perm[i]] (so the multiset is invariant)
    deg = (np.bincount(src, minlength=g.nv)
           + np.bincount(dst, minlength=g.nv))
    g2 = apply_perm(g, perm)
    s2, d2 = g2.edge_arrays()
    deg2 = (np.bincount(s2, minlength=g.nv)
            + np.bincount(d2, minlength=g.nv))
    assert np.array_equal(deg2, deg[perm])


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_reorder_numpy_fallback_contract(mode):
    """The toolchain-less fallback holds the same contract (not the
    same order — the C++ pass is the production one)."""
    g = _community(scale=9, ef=6)
    src, dst = g.edge_arrays()
    perm = native._reorder_cluster_numpy(
        src.astype(np.uint32), dst.astype(np.uint32), g.nv, mode)
    assert sorted(perm.tolist()) == list(range(g.nv))


def test_reorder_cluster_guards():
    with pytest.raises(ValueError, match="mode"):
        native.reorder_cluster(np.zeros(1, np.uint32),
                               np.zeros(1, np.uint32), 2,
                               mode="bogus")
    with pytest.raises(ValueError, match="outside"):
        native.reorder_cluster(np.array([5], np.uint32),
                               np.array([0], np.uint32), 2)


def test_page_reorder_trail_and_methods():
    """The driver scores every candidate against the plan builder's
    measured objective and never returns a worse-than-baseline order;
    method='none' is the identity."""
    g = _community(scale=11)
    g0, perm0, rep0 = page_reorder(g, method="none")
    assert g0 is g and np.array_equal(perm0, np.arange(g.nv))
    for method in ("degree", "native", "hillclimb"):
        g2, perm, rep = page_reorder(g, method=method)
        assert sorted(perm.tolist()) == list(range(g.nv))
        assert rep["chosen_fill"] >= rep["baseline_fill"]
        assert "none" in rep["candidates"]
        # the report's chosen fill IS the returned order's measured
        # fill (the inspection trail is honest)
        st = page_fill_stats(g2)
        assert rep["chosen_fill"] == pytest.approx(
            st["padded_fill"], abs=1e-2)
    with pytest.raises(ValueError, match="method"):
        page_reorder(g, method="bogus")


def test_acceptance_fill_recovers_past_break_even():
    """THE round-16 acceptance: the scrambled community shape starts
    in the R-MAT 6-12 fill band; the reorder pass lifts the plan
    builder's measured page_fill past the break-even 23, and
    ``gather="auto"`` then leaves the flat path (both the resolution
    rule and a real engine build)."""
    from lux_tpu.apps import pagerank
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.ops.pagegather import plan_paged_stats, resolve_gather
    from lux_tpu.scalemodel import page_break_even_fill

    g = community_graph(scale=14, edge_factor=8, community_scale=8,
                        seed=0)
    base = page_fill_stats(g)["padded_fill"]
    assert base < 13, "scramble must start in the R-MAT band"
    g2, _perm, rep = page_reorder(g, method="hillclimb")
    assert rep["chosen_fill"] >= 23
    assert rep["chosen_fill"] >= page_break_even_fill()

    sg = ShardedGraph.build(g2, 2, vpad_align=128)
    st = plan_paged_stats(sg, pagemajor=True)
    table = 4 * sg.num_parts * sg.vpad
    assert resolve_gather("auto", st, table) != "flat"
    # and on the UNREORDERED graph auto stays flat (the honest
    # round-15 negative, now an A/B inside one test)
    sg0 = ShardedGraph.build(g, 2, vpad_align=128)
    st0 = plan_paged_stats(sg0, pagemajor=True)
    assert resolve_gather("auto", st0, table) == "flat"

    eng = pagerank.build_engine(g2, num_parts=2, gather="auto")
    assert eng.gather in ("paged", "pagemajor")
    assert eng.page_plan is not None


# ---------------------------------------------------------------------
# permutation-invariance oracles: 4 apps, 1 and 8 devices


def _mesh8():
    from lux_tpu.parallel.mesh import make_mesh
    return make_mesh(8)


def _unmap(result, perm):
    """Map a reordered run's [nv, ...] result back to original ids:
    row new of the reordered run is original vertex perm[new]."""
    out = np.empty_like(result)
    out[np.asarray(perm)] = result
    return out


@pytest.mark.parametrize("np_mesh", [(2, False), (8, True)],
                         ids=["np2", "mesh8"])
def test_invariance_pagerank_colfilter_float(np_mesh):
    """Float (sum-reduce) apps: reorder + map-back equals the
    unreordered run to tight tolerance (sums re-associate across
    layouts, so bitwise is not the contract — same discipline as the
    paged parity tests)."""
    from lux_tpu.apps import colfilter, pagerank

    num_parts, use_mesh = np_mesh
    mesh = _mesh8() if use_mesh else None
    g = _community()
    g2, perm, _rep = page_reorder(g, method="native")

    eng = pagerank.build_engine(g, num_parts=num_parts, mesh=mesh)
    a = np.asarray(eng.unpad(eng.run(eng.init_state(), 5)))
    eng2 = pagerank.build_engine(g2, num_parts=num_parts,
                                 mesh=mesh, gather="auto")
    b = np.asarray(eng2.unpad(eng2.run(eng2.init_state(), 5)))
    np.testing.assert_allclose(_unmap(b, perm), a, rtol=2e-6,
                               atol=1e-9)

    gw = _community(weighted=True)
    gw2 = apply_perm(gw, perm)
    ec = colfilter.build_engine(gw, num_parts=num_parts, mesh=mesh)
    c = np.asarray(ec.unpad(ec.run(ec.init_state(), 3)))
    ec2 = colfilter.build_engine(gw2, num_parts=num_parts, mesh=mesh)
    d = np.asarray(ec2.unpad(ec2.run(ec2.init_state(), 3)))
    np.testing.assert_allclose(_unmap(d, perm), c, rtol=2e-5,
                               atol=1e-8)


@pytest.mark.parametrize("np_mesh", [(2, False), (8, True)],
                         ids=["np2", "mesh8"])
def test_invariance_sssp_components_bitwise(np_mesh):
    """Integer (min/max-reduce) apps: reorder + map-back is BITWISE
    equal to the unreordered run — min/max fixed points are
    order-independent, so any deviation is a real indexing bug."""
    from lux_tpu.apps import components, sssp

    num_parts, use_mesh = np_mesh
    mesh = _mesh8() if use_mesh else None
    gw = _community(weighted=True)
    g2, perm, _rep = page_reorder(gw, method="native")
    rank = np.empty(gw.nv, np.int64)
    rank[perm] = np.arange(gw.nv)

    start = 17
    ea = sssp.build_engine(gw, start, weighted=True,
                           num_parts=num_parts, mesh=mesh)
    la, aa = ea.init_state()
    la, _act, _it = ea.converge(la, aa)
    a = np.asarray(ea.unpad(la))
    eb = sssp.build_engine(g2, int(rank[start]), weighted=True,
                           num_parts=num_parts, mesh=mesh,
                           gather="auto")
    lb, ab = eb.init_state()
    lb, _act, _it = eb.converge(lb, ab)
    b = np.asarray(eb.unpad(lb))
    assert np.array_equal(_unmap(b, perm), a)

    # p_in=1.0: the scrambled communities ARE the components (32 of
    # them) — a far stronger partition-invariance probe than one
    # giant component
    giso = community_graph(scale=12, edge_factor=8,
                           community_scale=7, p_in=1.0, seed=4)
    s2, d2 = components.symmetrize(*giso.edge_arrays())
    gc = Graph.from_edges(s2.astype(np.uint32), d2.astype(np.uint32),
                          giso.nv)
    gc2 = apply_perm(gc, perm)
    ec = components.build_engine(gc, num_parts=num_parts, mesh=mesh,
                                 enable_sparse=False)
    lc, ac = ec.init_state()
    lc, _act, _it = ec.converge(lc, ac)
    c = np.asarray(ec.unpad(lc))
    ed = components.build_engine(gc2, num_parts=num_parts, mesh=mesh,
                                 enable_sparse=False, gather="auto")
    ld, ad = ed.init_state()
    ld, _act, _it = ed.converge(ld, ad)
    d = np.asarray(ed.unpad(ld))
    # component LABELS are representative vertex ids (max over the
    # component), and the max of the NEW ids is a different vertex —
    # the invariant is the PARTITION: the mapped-back labeling must
    # induce exactly the original equivalence classes (a bijection
    # between label values), checked bitwise on the canonicalized
    # labelings
    dm = _unmap(d, perm)

    def canonical(lab):
        # relabel every class by its smallest member index
        first = {}
        out = np.empty_like(lab)
        for i, v in enumerate(lab.tolist()):
            if v not in first:
                first[v] = i
            out[i] = first[v]
        return out

    assert np.array_equal(canonical(dm), canonical(c))


# ---------------------------------------------------------------------
# sidecar + load path


def test_sidecar_roundtrip_through_from_file(tmp_path):
    g = _community(scale=10)
    p = str(tmp_path / "g.lux")
    luxfmt.write_lux(p, g.row_ptrs, g.col_idx)
    _g2, perm, _rep = page_reorder(g, method="native")
    luxfmt.write_perm_sidecar(p, perm)
    loaded = Graph.from_file(p, reorder=True)
    want = apply_perm(g, perm)
    assert np.array_equal(loaded.col_idx, want.col_idx)
    assert np.array_equal(loaded.row_ptrs, want.row_ptrs)
    # auto: applies when present, identity when absent
    auto = Graph.from_file(p, reorder="auto")
    assert np.array_equal(auto.col_idx, want.col_idx)
    p2 = str(tmp_path / "bare.lux")
    luxfmt.write_lux(p2, g.row_ptrs, g.col_idx)
    bare = Graph.from_file(p2, reorder="auto")
    assert np.array_equal(np.asarray(bare.col_idx),
                          np.asarray(g.col_idx))
    with pytest.raises(luxfmt.GraphFormatError, match="perm"):
        Graph.from_file(p2, reorder=True)
    with pytest.raises(ValueError, match="reorder"):
        Graph.from_file(p, reorder="sometimes")


def test_sidecar_validation_typed_errors(tmp_path):
    g = _community(scale=9)
    p = str(tmp_path / "g.lux")
    luxfmt.write_lux(p, g.row_ptrs, g.col_idx)
    perm = np.random.default_rng(0).permutation(g.nv)
    sp = luxfmt.write_perm_sidecar(p, perm)
    assert np.array_equal(luxfmt.read_perm_sidecar(p, nv=g.nv), perm)
    # duplicate entry -> bijection check
    bad = perm.copy()
    bad[0] = bad[1]
    with pytest.raises(luxfmt.GraphFormatError) as e:
        luxfmt.validate_perm(bad, g.nv, "x")
    assert e.value.check == "perm_bijection"
    # wrong nv -> length check
    with pytest.raises(luxfmt.GraphFormatError) as e:
        luxfmt.read_perm_sidecar(p, nv=g.nv + 1)
    assert e.value.check == "perm_length"
    # truncated payload
    raw = open(sp, "rb").read()
    open(sp, "wb").write(raw[:-4])
    with pytest.raises(luxfmt.GraphFormatError) as e:
        luxfmt.read_perm_sidecar(p, nv=g.nv)
    assert e.value.check == "perm_length"
    # bad magic
    open(sp, "wb").write(b"XXXX" + raw[4:])
    with pytest.raises(luxfmt.GraphFormatError) as e:
        luxfmt.read_perm_sidecar(p, nv=g.nv)
    assert e.value.check == "perm_header"
    # a corrupt sidecar cannot be WRITTEN either
    with pytest.raises(luxfmt.GraphFormatError):
        luxfmt.write_perm_sidecar(p, bad)


def test_fsck_reports_sidecar(tmp_path):
    g = _community(scale=9)
    p = str(tmp_path / "g.lux")
    luxfmt.write_lux(p, g.row_ptrs, g.col_idx)
    fsck = str(REPO / "scripts" / "fsck_lux.py")
    r = subprocess.run([sys.executable, fsck, p],
                       capture_output=True, text=True)
    assert r.returncode == 0 and "perm=no" in r.stdout
    luxfmt.write_perm_sidecar(p, np.arange(g.nv))
    r = subprocess.run([sys.executable, fsck, p],
                       capture_output=True, text=True)
    assert r.returncode == 0 and "perm=yes" in r.stdout
    # torn sidecar fails the file
    with open(p + ".perm", "r+b") as f:
        f.seek(9)
        f.write(b"\xff\xff\xff")
    r = subprocess.run([sys.executable, fsck, p],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "perm_" in r.stderr


# ---------------------------------------------------------------------
# bench gather-ab reorder lines -> check_bench


def test_bench_gather_ab_reorder_lines(tmp_path):
    """The acceptance instrument end-to-end (in-process, tiny shape):
    bench.run_config produces the reordered + paired none gather-ab
    lines on the community shape; the reordered line's measured
    page_fill crosses the break-even, auto selects the page-binned
    path, and scripts/check_bench.py ACCEPTS the artifact (schema +
    the fill-not-decreased pairing rule)."""
    import argparse

    sys.path.insert(0, str(REPO))
    import bench

    args = argparse.Namespace(
        scale=13, ef=8, np=1, ni=2, repeats=1, pair=0, verbose=False,
        health=False, audit="warn", shape="community",
        reorder="hillclimb", batch="1")
    lines = []
    for cfg in ("gather-ab@paged", "gather-ab@flat",
                "gather-ab@paged:hillclimb",
                "gather-ab@flat:hillclimb"):
        name, samples, extra, _rerun = bench.run_config(cfg, args)
        value = round(float(np.median(samples)), 4)
        line = dict(metric=name + "_gteps_per_chip", value=value,
                    unit="GTEPS", vs_baseline=value,
                    samples=[round(s, 4) for s in samples],
                    attempts=len(samples), discarded=[], **extra)
        lines.append(line)
    by = {ln["metric"]: ln for ln in lines}
    pn = by["pagerank_paged_comm13_gteps_per_chip"]
    pr = by["pagerank_paged_hillclimb_comm13_gteps_per_chip"]
    assert pn["reorder"] == "none" and pr["reorder"] == "hillclimb"
    assert pr["page_fill"] >= 23 > pn["page_fill"]
    assert pr["page_ratio"] > 0

    out = tmp_path / "bench.jsonl"
    out.write_text("".join(json.dumps(ln) + "\n" for ln in lines))
    chk = str(REPO / "scripts" / "check_bench.py")
    r = subprocess.run([sys.executable, chk, "-legacy-ok", str(out)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    # the pairing rule rejects a published pair whose fill DROPPED
    pr_bad = dict(pr, page_fill=pn["page_fill"] - 1)
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(json.dumps(ln) + "\n"
                           for ln in [pn, pr_bad]))
    r = subprocess.run([sys.executable, chk, "-legacy-ok", str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "DECREASED" in r.stderr
