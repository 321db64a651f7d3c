"""Tests for the padded part-major device layout (ShardedGraph)."""

import numpy as np
import pytest

from lux_tpu.convert import rmat_edges, uniform_random_edges
from lux_tpu.graph import Graph, ShardedGraph


@pytest.mark.parametrize("num_parts", [1, 3, 8])
def test_layout_roundtrip(num_parts):
    src, dst = uniform_random_edges(200, 1500, seed=7)
    g = Graph.from_edges(src, dst, 200)
    sg = ShardedGraph.build(g, num_parts)
    x = np.random.default_rng(0).random(200).astype(np.float32)
    np.testing.assert_array_equal(sg.from_padded(sg.to_padded(x)), x)


@pytest.mark.parametrize("num_parts", [1, 2, 5])
def test_edges_reconstruct_graph(num_parts):
    """Every original edge appears exactly once in the padded layout,
    with src_slot/dst_local translating back to the original ids."""
    src, dst = uniform_random_edges(100, 800, seed=11)
    g = Graph.from_edges(src, dst, 100)
    sg = ShardedGraph.build(g, num_parts)

    got = []
    for p in range(num_parts):
        nep = int(sg.ne_part[p])
        for e in range(nep):
            slot = int(sg.src_slot[p, e])
            sp, sl = divmod(slot, sg.vpad)
            s_global = int(sg.starts[sp]) + sl
            d_global = int(sg.starts[p]) + int(sg.dst_local[p, e])
            got.append((s_global, d_global))
        # padding edges must point at the trash segment
        assert np.all(sg.dst_local[p, nep:] == sg.vpad)
    want = sorted(zip(src.tolist(), dst.tolist()))
    assert sorted(got) == want


def test_dst_local_sorted_within_part():
    """Edges stay dst-sorted per part — the invariant the segmented
    reductions and Pallas kernels rely on."""
    src, dst, nv = rmat_edges(scale=10, edge_factor=8, seed=2)
    g = Graph.from_edges(src, dst, nv)
    sg = ShardedGraph.build(g, 4)
    for p in range(4):
        nep = int(sg.ne_part[p])
        d = sg.dst_local[p, :nep]
        assert np.all(np.diff(d.astype(np.int64)) >= 0)


def test_row_ptr_local_consistent():
    src, dst = uniform_random_edges(123, 999, seed=5)
    g = Graph.from_edges(src, dst, 123)
    sg = ShardedGraph.build(g, 3)
    for p in range(3):
        nvp = int(sg.nv_part[p])
        nep = int(sg.ne_part[p])
        rpl = sg.row_ptr_local[p]
        assert rpl[0] == 0
        assert rpl[nvp] == nep
        assert np.all(np.diff(rpl) >= 0)
        # in-degree run-lengths match dst_local runs
        in_deg = np.diff(rpl[:nvp + 1])
        counts = np.bincount(sg.dst_local[p, :nep], minlength=sg.vpad + 1)
        np.testing.assert_array_equal(in_deg, counts[:nvp])


def test_weighted_layout():
    src, dst, w = uniform_random_edges(60, 500, seed=9, weighted=True)
    g = Graph.from_edges(src, dst, 60, weights=w)
    sg = ShardedGraph.build(g, 2)
    assert sg.weighted and sg.edge_weight is not None
    tot = sum(float(sg.edge_weight[p, :int(sg.ne_part[p])].sum())
              for p in range(2))
    assert tot == pytest.approx(float(np.asarray(w).sum()))
    # padding weights are zero
    for p in range(2):
        assert np.all(sg.edge_weight[p, int(sg.ne_part[p]):] == 0)


def test_memory_report():
    src, dst = uniform_random_edges(100, 700, seed=1)
    g = Graph.from_edges(src, dst, 100)
    sg = ShardedGraph.build(g, 4)
    rep = sg.memory_report()
    assert rep["total_bytes"] > 0 and rep["num_parts"] == 4
    assert rep["push_sparse_bytes_per_part"] == 0

    # the push fit plan: sparse view prices the second edge array
    push = sg.memory_report(push_sparse=True)
    assert push["push_sparse_bytes_per_part"] >= sg.epad * 4
    assert push["total_bytes"] > rep["total_bytes"]

    # owner pricing uses the real (padded) slot count when given;
    # packed (one uint32/slot) is inferred for small vpad, classic
    # (int32 + int8) on request
    own = sg.memory_report(exchange="owner",
                           owner_slots_per_part=2 * sg.epad)
    assert own["edge_bytes_per_part"] == 2 * sg.epad * 4
    classic = sg.memory_report(exchange="owner",
                               owner_slots_per_part=2 * sg.epad,
                               owner_packed=False)
    assert classic["edge_bytes_per_part"] == 2 * sg.epad * 5


def test_src_sorted_compressed_index_oracle():
    """The compressed source index must list exactly each part's edges
    grouped by global source (the dense nv-wide row-pointer oracle),
    and be much smaller than nv on graphs with few distinct sources."""
    rng = np.random.default_rng(4)
    nv, ne = 400, 900
    src = rng.integers(0, 40, ne)        # only 40 possible sources
    dst = rng.integers(0, nv, ne)
    g = Graph.from_edges(src, dst, nv)
    sg = ShardedGraph.build(g, 3)
    ss = sg.src_sorted()
    S = ss["src_ids"].shape[1]
    assert S <= 40                        # compressed far below nv
    for p in range(3):
        v0 = int(sg.starts[p])
        # oracle: per-part in-part out-edge lists by global source
        gsrc, gdst = g.edge_arrays()
        in_part = (gdst >= v0) & (gdst < int(sg.starts[p + 1]))
        want = {}
        for s, d in zip(gsrc[in_part], gdst[in_part]):
            want.setdefault(int(s), []).append(int(d) - v0)
        ids, off = ss["src_ids"][p], ss["src_off"][p]
        got = {}
        for i, s in enumerate(ids):
            if s == sg.nv:
                break
            got[int(s)] = sorted(
                ss["ss_dst"][p, off[i]:off[i + 1]].tolist())
        assert got == {k: sorted(v) for k, v in want.items()}
    # explicit s_pad: too small -> error; larger -> padded shape
    import pytest as _pytest
    with _pytest.raises(ValueError):
        sg.src_sorted(s_pad=1)
    assert sg.src_sorted(s_pad=64)["src_ids"].shape[1] == 64


@pytest.mark.parametrize("weighted", [False, True])
def test_memory_report_of_a_loaded_sparse_view_is_the_built_ones(
        tmp_path, monkeypatch, weighted):
    """A src-sorted view LOADED from the preparation store sets the
    field the advisor prices the compressed source index from, so its
    report is the built view's and not the no-view upper bound."""
    from lux_tpu import prepstore
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(tmp_path / "store"))
    monkeypatch.setattr(prepstore, "MIN_EDGES", 0)
    rng = np.random.default_rng(4)
    nv, ne = 400, 900
    src = rng.integers(0, 40, ne)        # few sources: S far below nv
    dst = rng.integers(0, nv, ne)
    w = rng.integers(1, 6, ne).astype(np.float32) if weighted else None

    def layout():
        return ShardedGraph.build(
            Graph.from_edges(src, dst, nv, weights=w), 3)

    bound = layout().memory_report(push_sparse=True)
    built = layout()
    built.src_sorted()
    loaded = layout()
    assert loaded._src_sorted_cache is None
    loaded.src_unique_max()              # a hit: nothing is sorted
    assert loaded._src_sorted_cache is not None
    want = built.memory_report(push_sparse=True)
    assert loaded.memory_report(push_sparse=True) == want
    assert (want["push_sparse_bytes_per_part"]
            < bound["push_sparse_bytes_per_part"])
