"""The preparation store (lux_tpu/prepstore.py) and its three call
sites: graph.pair_relabel, ops/pairs.plan_sharded_pairs,
ShardedGraph._src_sorted_raw.  Everything lives in ``tmp_path``."""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from lux_tpu import prepstore, telemetry
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
from lux_tpu.ops.pairs import W, plan_sharded_pairs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store in ``tmp_path`` that engages on graphs of any size."""
    d = tmp_path / "store"
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(d))
    monkeypatch.setattr(prepstore, "MIN_EDGES", 0)
    return d


def edges(weighted=False, seed=7, nv=4 * W, ne=9000):
    rng = np.random.default_rng(seed)
    src = ((rng.zipf(1.3, ne) - 1) % nv).astype(np.uint32)
    dst = ((rng.zipf(1.2, ne) - 1) % nv).astype(np.uint32)
    w = rng.integers(1, 6, ne).astype(np.float32) if weighted else None
    return src, dst, nv, w


def graph(weighted=False, **kw):
    """A fresh Graph object each call: a hit has to come from the
    CONTENT, not from anything kept on the object."""
    src, dst, nv, w = edges(weighted, **kw)
    return Graph.from_edges(src, dst, nv, weights=w)


def lookups(tip):
    """[(hit, miss)] of the ``prep.store`` records since ``tip``."""
    return [(r["counts"]["hit"], r["counts"]["miss"])
            for r in telemetry.spans()
            if r["id"] > tip and r["name"] == "prep.store"]


def ring_tip():
    """Id of a fresh mark: ids are given when a span opens, so every
    record of a later span has a larger one."""
    telemetry.mark("test.tip")
    return telemetry.spans()[-1]["id"]


def entries(store):
    return sorted(os.listdir(store)) if store.exists() else []


def same(a, b):
    if a is None or b is None:
        assert a is b
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a, b)


def prepare(g, num_parts, pair_threshold=4, threshold=4, min_fill=None,
            kdim=1, vpad_cap=1.2, sparse=False):
    """relabel -> layout -> plan (-> sparse view): what an engine's
    construction calls, product by product (``pair_threshold`` is the
    relabel's and the layout's, ``threshold`` the planner's)."""
    g2, perm, starts = pair_relabel(g, num_parts,
                                    pair_threshold=pair_threshold,
                                    vpad_cap=vpad_cap)
    sg = ShardedGraph.build(g2, num_parts, starts=starts,
                            pair_threshold=pair_threshold)
    sp, res = plan_sharded_pairs(sg, threshold, min_fill=min_fill,
                                 kdim=kdim)
    ss = sg.src_sorted() if sparse else None
    return dict(g2=g2, perm=perm, starts=starts, sg=sg, sp=sp, res=res,
                ss=ss)


def assert_same_products(a, b):
    for n in ("row_ptrs", "col_idx", "weights", "out_degrees"):
        same(getattr(a["g2"], n), getattr(b["g2"], n))
    same(a["perm"], b["perm"])
    same(a["starts"], b["starts"])
    sa, sb = a["sp"], b["sp"]
    for n in ("rowbind", "rel_dst", "weight", "tile_pos", "row_tile"):
        same(getattr(sa, n), getattr(sb, n))
    for n in ("classes", "stats", "n_tiles", "n_slots", "R", "Rp"):
        assert getattr(sa, n) == getattr(sb, n), n
    assert all(isinstance(c, tuple) for c in sb.classes)
    for n in ("src_slot", "dst_local", "edge_weight", "row_ptr_local",
              "ne_part", "starts", "vmask", "deg_padded"):
        same(getattr(a["res"], n), getattr(b["res"], n))
    for n in ("epad", "vpad", "nv", "ne", "num_parts", "weighted",
              "max_out_degree"):
        assert getattr(a["res"], n) == getattr(b["res"], n), n
    if a["ss"] is not None:
        for n in ("src_ids", "src_off", "ss_dst", "ss_weight"):
            same(a["ss"][n], b["ss"][n])
        assert a["ss"]["max_in_deg"] == b["ss"]["max_in_deg"]


# ---- (a) a hit is the miss's product --------------------------------


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("product,num_parts", [
    ("relabel", 1), ("relabel", 4), ("pair_plan", 1), ("pair_plan", 4),
    ("src_sorted", 2)])
def test_a_hit_equals_the_miss(store, product, num_parts, weighted):
    tip = ring_tip()
    miss = prepare(graph(weighted), num_parts, sparse=True)
    assert lookups(tip) == [(0, 1)] * 3
    assert len(entries(store)) == 3
    # leave only the entry under test, so that the other two products
    # are computed again and this one is loaded
    for e in entries(store):
        if not e.startswith(product):
            os.rename(store / e, store / (e + ".away"))
    tip = ring_tip()
    hit = prepare(graph(weighted), num_parts, sparse=True)
    want = {"relabel": [(1, 0), (0, 1), (0, 1)],
            "pair_plan": [(0, 1), (1, 0), (0, 1)],
            "src_sorted": [(0, 1), (0, 1), (1, 0)]}[product]
    assert lookups(tip) == want
    assert miss["sp"] is not None and miss["sp"].stats["covered"] > 0
    assert_same_products(miss, hit)


def test_no_pair_anywhere_is_stored_too(store):
    from lux_tpu.convert import uniform_random_edges

    def sparse_graph():
        src, dst = uniform_random_edges(8 * W, 300, seed=2)
        return Graph.from_edges(src, dst, 8 * W)

    first = prepare(sparse_graph(), 1, pair_threshold=64, threshold=64)
    assert first["sp"] is None and first["res"] is first["sg"]
    tip = ring_tip()
    again = prepare(sparse_graph(), 1, pair_threshold=64, threshold=64)
    assert lookups(tip) == [(1, 0), (1, 0)]
    assert again["sp"] is None and again["res"] is again["sg"]


def test_one_sparse_entry_serves_every_s_pad(store):
    """The stored view is the raw one: ``s_pad`` pads it after the
    load, so it is no part of the key."""
    built = prepare(graph(), 2)["sg"]
    want = built.src_sorted(s_pad=700)
    n = len(entries(store))
    loaded = prepare(graph(), 2)["sg"]
    tip = ring_tip()
    got = loaded.src_sorted(s_pad=700)
    assert lookups(tip) == [(1, 0)] and len(entries(store)) == n
    for k in ("src_ids", "src_off", "ss_dst"):
        same(want[k], got[k])
    assert got["src_ids"].shape[1] == 700
    assert loaded.src_unique_max() == built.src_unique_max()


@pytest.mark.parametrize("app,num_parts,weighted", [
    ("pagerank", 1, False), ("pagerank", 4, False), ("sssp", 1, True),
    ("sssp", 2, False)])
def test_an_engine_from_a_hit_answers_bitwise_alike(store, app,
                                                    num_parts, weighted):
    from lux_tpu.apps import pagerank, sssp

    def answer():
        g2, _perm, starts = pair_relabel(graph(weighted), num_parts,
                                         pair_threshold=4)
        sg = ShardedGraph.build(g2, num_parts, starts=starts,
                                pair_threshold=4)
        if app == "pagerank":
            eng = pagerank.build_engine(g2, num_parts, sg=sg,
                                        pair_threshold=4)
            assert eng.pairs is not None
            return eng.unpad(eng.run(eng.init_state(), 6))
        eng = sssp.build_engine(g2, start_vertex=1, num_parts=num_parts,
                                weighted=weighted, sg=sg,
                                pair_threshold=4)
        assert eng.pairs is not None and eng.enable_sparse
        labels, _iters = eng.run()
        return labels

    n = 3 if app == "sssp" else 2       # relabel, plan (, sparse view)
    tip = ring_tip()
    first = answer()
    assert lookups(tip) == [(0, 1)] * n
    tip = ring_tip()
    second = answer()
    assert lookups(tip) == [(1, 0)] * n
    same(first, second)


# ---- (b) the key is content and parameters, never a path ------------


def test_graph_key_moves_with_one_edge_and_one_weight():
    src, dst, nv, w = edges(weighted=True)
    key = Graph.from_edges(src, dst, nv, weights=w).content_key()
    assert key == Graph.from_edges(src, dst, nv, weights=w).content_key()
    src2 = src.copy()
    src2[17] = (src2[17] + 1) % nv
    assert Graph.from_edges(src2, dst, nv, weights=w).content_key() != key
    w2 = w.copy()
    w2[17] += 1
    assert Graph.from_edges(src, dst, nv, weights=w2).content_key() != key
    assert Graph.from_edges(src, dst, nv).content_key() != key


def test_graph_key_follows_a_reassigned_field():
    g = graph(weighted=True)
    key = g.content_key()
    assert g.content_key() == key
    g.weights = g.weights + 1
    assert g.content_key() != key


def test_graph_key_ignores_path_and_mtime(tmp_path):
    from lux_tpu.format import write_lux
    g = graph()
    paths = [tmp_path / "a.lux", tmp_path / "elsewhere" / "b.lux"]
    os.makedirs(paths[1].parent)
    for p in paths:
        write_lux(str(p), g.row_ptrs, g.col_idx, degrees=g.out_degrees)
    os.utime(paths[1], (1, 1))
    keys = {Graph.from_file(str(p)).content_key() for p in paths}
    assert keys == {g.content_key()}


@pytest.mark.parametrize("moved", [
    "num_parts", "pair_threshold", "vpad_cap",      # the relabel's
    "threshold", "min_fill", "kdim",                # the plan's
    "format_version"])
def test_key_moves_with_each_parameter(store, monkeypatch, moved):
    base = dict(num_parts=2, pair_threshold=4, threshold=4,
                min_fill=None, kdim=1, vpad_cap=1.2)
    prepare(graph(), sparse=True, **base)
    tip = ring_tip()
    prepare(graph(), sparse=True, **base)
    assert lookups(tip) == [(1, 0)] * 3
    changed = dict(base)
    if moved == "format_version":
        monkeypatch.setattr(prepstore, "FORMAT_VERSION",
                            prepstore.FORMAT_VERSION + 1)
    else:
        changed[moved] = dict(num_parts=4, pair_threshold=5,
                              vpad_cap=2.0, threshold=5, min_fill=9,
                              kdim=20)[moved]
    # an input reaches every product downstream of it: the planner's
    # own parameters its entry alone
    plan_only = moved in ("threshold", "min_fill", "kdim")
    tip = ring_tip()
    prepare(graph(), sparse=True, **changed)
    assert lookups(tip) == ([(1, 0), (0, 1), (1, 0)] if plan_only
                            else [(0, 1)] * 3)


# ---- (c) an entry that does not load is a miss, rebuilt -------------


def _truncate(d):
    path = os.path.join(d, "rel_dst.npy")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)


def _drop_array(d):
    os.remove(os.path.join(d, "rowbind.npy"))


def _foreign_version(d):
    import json
    path = os.path.join(d, "meta.json")
    with open(path) as f:
        doc = json.load(f)
    doc["version"] = 0
    with open(path, "w") as f:
        json.dump(doc, f)


def _garbage_meta(d):
    with open(os.path.join(d, "meta.json"), "w") as f:
        f.write("{not json")


@pytest.mark.parametrize("damage", [_truncate, _drop_array,
                                    _foreign_version, _garbage_meta])
def test_a_damaged_entry_is_a_miss_that_is_rebuilt(store, damage):
    sound = prepare(graph(), 2)
    plan, = [e for e in entries(store) if e.startswith("pair_plan")]
    damage(store / plan)
    tip = ring_tip()
    rebuilt = prepare(graph(), 2)
    assert lookups(tip) == [(1, 0), (0, 1)]
    assert_same_products(sound, rebuilt)
    # overwritten: one sound entry, nothing left beside it
    assert entries(store).count(plan) == 1
    assert not [e for e in entries(store)
                if e.endswith((".partial", ".stale"))]
    tip = ring_tip()
    prepare(graph(), 2)
    assert lookups(tip) == [(1, 0), (1, 0)]


def test_an_unwritable_store_is_no_error(tmp_path, monkeypatch):
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(blocker))
    monkeypatch.setattr(prepstore, "MIN_EDGES", 0)
    p = prepare(graph(), 1)
    assert p["sp"] is not None
    assert prepstore.get("pair_plan", "0" * 64) is None


# ---- (d) two writers of one key -------------------------------------


_WRITER = """
import sys
import numpy as np
from lux_tpu import prepstore
rng = np.random.default_rng(5)
arrays = {"a": rng.integers(0, 9, 300000).astype(np.int32),
          "b": rng.random(1000).astype(np.float32), "c": None}
for _ in range(int(sys.argv[1])):
    prepstore.put("race", "k" * 64, arrays, {"n": 3})
    got = prepstore.get("race", "k" * 64)
    # a reader sees a whole entry or none, never half of one
    assert got is None or (sorted(got[0]) == ["a", "b"]
                           and np.array_equal(got[0]["a"], arrays["a"]))
"""


def test_two_processes_putting_one_key_leave_one_sound_entry(store):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               LUX_PREP_STORE_DIR=str(store))
    procs = [subprocess.Popen([sys.executable, "-c", _WRITER, "25"],
                              env=env, cwd=REPO,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(3)]
    for p in procs:
        _out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err
    assert entries(store) == ["race-" + "k" * 40]
    arrays, meta = prepstore.get("race", "k" * 64)
    assert meta == {"n": 3} and sorted(arrays) == ["a", "b"]
    rng = np.random.default_rng(5)
    same(arrays["a"], rng.integers(0, 9, 300000).astype(np.int32))


# ---- (e) when the store stays away ----------------------------------


def test_a_graph_under_the_size_constant_leaves_no_trace(tmp_path,
                                                         monkeypatch):
    d = tmp_path / "store"
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(d))
    g = graph()
    assert not prepstore.engages(g.ne)
    tip = ring_tip()
    p = prepare(g, 2, sparse=True)
    assert lookups(tip) == [] and not d.exists()
    assert p["sg"].content_key is None and g._key_cache is None


def test_a_local_parts_build_leaves_no_trace(store):
    g = graph()
    sg = ShardedGraph.build(g, 2, vpad_align=128, parts=[0, 1])
    assert sg.local_parts is not None and sg.content_key is None
    tip = ring_tip()
    sp, res = plan_sharded_pairs(sg, 4)
    sg.src_sorted()
    assert sp is not None and res.content_key is None
    assert lookups(tip) == [] and entries(store) == []
    # a key on a local-parts layout (it has none today) changes nothing
    keyed = dataclasses.replace(sg, content_key="f" * 64,
                                _src_sorted_cache=None)
    plan_sharded_pairs(keyed, 4)
    keyed.src_sorted()
    assert lookups(tip) == [] and entries(store) == []


def test_the_residual_layout_has_a_key_of_its_own(store):
    """The residual's sparse view is not the full layout's."""
    p = prepare(graph(), 1)
    assert p["res"].content_key not in (None, p["sg"].content_key)
    full = p["sg"].src_sorted()
    resid = p["res"].src_sorted()
    assert int(resid["src_off"].max()) < int(full["src_off"].max())
    again = prepare(graph(), 1)
    assert again["res"].content_key == p["res"].content_key
    same(again["res"].src_sorted()["ss_dst"], resid["ss_dst"])


def test_default_directory_is_the_checkouts_and_the_variable_wins(
        monkeypatch, tmp_path):
    from lux_tpu import runtime
    monkeypatch.delenv("LUX_PREP_STORE_DIR", raising=False)
    assert runtime.prep_store_dir() == os.path.join(REPO, ".prep_store")
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(tmp_path))
    assert runtime.prep_store_dir() == str(tmp_path)


# ---- (f) the spans keep their counts on a hit -----------------------


def test_pair_plan_span_carries_its_counts_on_a_hit(store):
    def plan_span():
        tip = ring_tip()
        p = prepare(graph(), 2)
        recs = [r for r in telemetry.spans() if r["id"] > tip]
        span, = [r for r in recs if r["name"] == "build.pair_plan"]
        kids = [r for r in recs if r["parent"] == span["id"]]
        return p, span, kids

    _p, missed, kids = plan_span()
    assert [k["name"] for k in kids] == ["prep.store", "prep.store.put"]
    assert kids[1]["counts"]["bytes"] > 0
    p, hit, kids = plan_span()
    assert [k["name"] for k in kids] == ["prep.store"]
    assert kids[0]["counts"] == dict(hit=1, miss=0,
                                     bytes=kids[0]["counts"]["bytes"])
    assert kids[0]["counts"]["bytes"] > 0
    assert hit["counts"] == missed["counts"]
    assert hit["counts"]["pair_edges"] == p["sp"].stats["covered"] > 0
    assert (hit["counts"]["pair_edges"]
            + hit["counts"]["residual_edges"]) == p["g2"].ne


def test_relabel_span_has_stages_on_a_miss_and_none_on_a_hit(store,
                                                             capsys):
    def stages():
        tip = ring_tip()
        pair_relabel(graph(), 2, pair_threshold=4, verbose=True)
        recs = [r for r in telemetry.spans() if r["id"] > tip]
        span, = [r for r in recs if r["name"] == "relabel"]
        return [r["name"] for r in recs if r["parent"] == span["id"]]

    assert stages() == ["prep.store", "relabel.degree_sort",
                        "relabel.pair_histogram", "relabel.deal",
                        "relabel.rebuild_csc", "prep.store.put"]
    assert stages() == ["prep.store"]
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("# pair_relabel/")]
    # -verbose prints the stages that are there: the miss's
    assert [ln.split("/")[1].split(":")[0] for ln in printed] == [
        "degree_sort", "pair_histogram", "deal", "rebuild_csc"]


# ---- the symmetry bit (PR 33): an entry of its own -------------------


def sym_edges(weighted=False, seed=11, nv=3 * W, ne=4000):
    """A symmetrized edge list with duplicates (zipf draws repeat
    pairs) and self-loops (mirrored onto themselves, so doubled)."""
    src, dst, nv, w = edges(weighted, seed=seed, nv=nv, ne=ne)
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    loops = np.arange(0, nv, 37)
    src, dst = (np.concatenate([src, loops]),
                np.concatenate([dst, loops]))
    if w is not None:
        w = np.concatenate([w, np.full(loops.size, 2, np.float32)])
    assert (src == dst).any() and \
        np.unique(src * nv + dst).size < src.size
    return (np.concatenate([src, dst]), np.concatenate([dst, src]), nv,
            None if w is None else np.concatenate([w, w]))


def _sharded(src, dst, nv, w, num_parts):
    return ShardedGraph.build(Graph.from_edges(src, dst, nv, weights=w),
                              num_parts)


@pytest.mark.parametrize("num_parts", [1, 3])
@pytest.mark.parametrize("case,want", [
    ("symmetrized", True), ("one-edge-dropped", False),
    ("balanced-cycle", False), ("weighted", True),
    ("one-weight-changed", False), ("directed", False)])
def test_the_symmetry_bit(store, case, want, num_parts):
    """True for symmetrized inputs with duplicates and self-loops
    (weights mirrored too), false after one edge is dropped, for a
    directed cycle laid over a symmetric graph (every vertex's in- and
    out-degree still equal, so only the key comparison can tell), and
    after one mirror's weight is changed."""
    weighted = case in ("weighted", "one-weight-changed")
    src, dst, nv, w = sym_edges(weighted)
    if case == "one-edge-dropped":
        keep = np.flatnonzero(src != dst)[5]
        src, dst = np.delete(src, keep), np.delete(dst, keep)
    elif case == "balanced-cycle":
        src = np.concatenate([src, [3, 50, 200]])
        dst = np.concatenate([dst, [50, 200, 3]])
    elif case == "one-weight-changed":
        w = w.copy()
        w[np.flatnonzero(src != dst)[5]] += 1
    elif case == "directed":
        src, dst, nv, w = edges()
    sg = _sharded(src, dst, nv, w, num_parts)
    assert sg.edges_symmetric() is want
    # the degree test alone settles a graph whose degrees differ: it
    # never reaches the store
    reached_store = case not in ("one-edge-dropped", "directed")
    assert any(e.startswith("symmetric-") for e in entries(store)) \
        == reached_store
    # a second process: the bit comes from the store
    tip = ring_tip()
    assert _sharded(src, dst, nv, w, num_parts).edges_symmetric() is want
    assert lookups(tip) == ([(1, 0)] if reached_store else [])


def test_a_store_from_before_the_bit_still_hits(store):
    """The bit is an entry of its own: the format version did not
    move, so every entry a store held before it (relabel, pair plan,
    src-sorted view) is still a hit, and the one new lookup is a miss
    that is written once."""
    assert prepstore.FORMAT_VERSION == 1
    src, dst, nv, _w = sym_edges()

    def build():
        got = prepare(Graph.from_edges(src, dst, nv), 2, sparse=True)
        return got, got["sg"].edges_symmetric()

    first, bit = build()
    assert bit is True
    held = entries(store)
    assert sorted(e.split("-")[0] for e in held) == \
        ["pair_plan", "relabel", "src_sorted", "symmetric"]
    # a store as the parent commit left it: no such entry yet
    import shutil
    for e in held:
        if e.startswith("symmetric-"):
            shutil.rmtree(store / e)
    tip = ring_tip()
    again, bit = build()
    assert bit is True
    assert lookups(tip) == [(1, 0), (1, 0), (1, 0), (0, 1)]
    assert entries(store) == held
    assert_same_products(first, again)
    tip = ring_tip()
    build()
    assert lookups(tip) == [(1, 0)] * 4
