"""Tiled scatter-free segment reduction vs. the scatter oracle.

The tiled layout (ops/tiled.py) must be bit-compatible in structure
with ``ops.segment.segment_reduce`` for every reduction kind, payload
rank, skew pattern, and partition count — it replaces the hot loop.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu.convert import rmat_edges, uniform_random_edges
from lux_tpu.graph import Graph, ShardedGraph
from lux_tpu.ops.segment import segment_reduce
from lux_tpu.ops.tiled import TiledLayout, tiled_segment_reduce


def _sharded(nv, ne, num_parts, seed=0):
    src, dst = uniform_random_edges(nv, ne, seed=seed)
    g = Graph.from_edges(src, dst, nv)
    return ShardedGraph.build(g, num_parts)


def _oracle(msgs, sg, p, kind):
    return np.asarray(segment_reduce(
        jnp.asarray(msgs), jnp.asarray(sg.dst_local[p]),
        sg.vpad + 1, kind)[:sg.vpad])


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("num_parts", [1, 3])
def test_matches_scatter_oracle(kind, num_parts):
    sg = _sharded(300, 2500, num_parts)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            W=16, E=32)
    rng = np.random.default_rng(0)
    msgs_flat = rng.random((sg.num_parts, sg.epad)).astype(np.float32)
    # padding edges must carry the identity in the flat oracle too
    if kind != "sum":
        ident = np.inf if kind == "min" else -np.inf
        msgs_flat = np.where(sg.dst_local < sg.vpad, msgs_flat, ident)
    else:
        msgs_flat = np.where(sg.dst_local < sg.vpad, msgs_flat, 0.0)
    msgs_ch = lay.chunk(msgs_flat)
    for p in range(sg.num_parts):
        got = np.asarray(tiled_segment_reduce(
            jnp.asarray(msgs_ch[p]), lay, jnp.asarray(lay.chunk_start[p]),
            jnp.asarray(lay.last_chunk[p]), jnp.asarray(lay.rel_dst[p]),
            sg.vpad, kind))
        want = _oracle(msgs_flat[p], sg, p, kind)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_skewed_hub_graph_needs_scan():
    """A hub vertex forces multi-chunk tiles; scan path must be exact."""
    nv, ne = 64, 5000
    rng = np.random.default_rng(1)
    dst = np.where(rng.random(ne) < 0.6, 7,
                   rng.integers(0, nv, ne)).astype(np.uint32)
    src = rng.integers(0, nv, ne, dtype=np.uint32)
    g = Graph.from_edges(src, dst, nv)
    sg = ShardedGraph.build(g, 2)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            W=8, E=16)
    assert lay.needs_scan
    msgs = np.where(sg.dst_local < sg.vpad, 1.0, 0.0).astype(np.float32)
    msgs_ch = lay.chunk(msgs)
    for p in range(sg.num_parts):
        got = np.asarray(tiled_segment_reduce(
            jnp.asarray(msgs_ch[p]), lay, jnp.asarray(lay.chunk_start[p]),
            jnp.asarray(lay.last_chunk[p]), jnp.asarray(lay.rel_dst[p]),
            sg.vpad, "sum"))
        want = _oracle(msgs[p], sg, p, "sum")
        np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("use_mxu", [False, True])
def test_vector_payload(use_mxu):
    """Colfilter-style [., K] payloads, both VPU and MXU strategies."""
    sg = _sharded(120, 900, 2, seed=3)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            W=16, E=64)
    K = 5
    rng = np.random.default_rng(2)
    msgs = rng.random((sg.num_parts, sg.epad, K)).astype(np.float32)
    msgs = np.where((sg.dst_local < sg.vpad)[..., None], msgs, 0.0)
    msgs_ch = lay.chunk(msgs)
    for p in range(sg.num_parts):
        got = np.asarray(tiled_segment_reduce(
            jnp.asarray(msgs_ch[p]), lay, jnp.asarray(lay.chunk_start[p]),
            jnp.asarray(lay.last_chunk[p]), jnp.asarray(lay.rel_dst[p]),
            sg.vpad, "sum", use_mxu=use_mxu))
        want = np.asarray(segment_reduce(
            jnp.asarray(msgs[p]), jnp.asarray(sg.dst_local[p]),
            sg.vpad + 1, "sum")[:sg.vpad])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_rmat_roundtrip_int():
    """Integer min-reduction (SSSP labels) on a power-law graph."""
    src, dst, nv = rmat_edges(scale=8, edge_factor=6, seed=5)
    g = Graph.from_edges(src, dst, nv)
    sg = ShardedGraph.build(g, 4)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            W=32, E=128)
    rng = np.random.default_rng(4)
    msgs = rng.integers(0, 1000, (sg.num_parts, sg.epad)).astype(np.int32)
    msgs = np.where(sg.dst_local < sg.vpad, msgs,
                    np.iinfo(np.int32).max)
    msgs_ch = lay.chunk(msgs)
    for p in range(sg.num_parts):
        got = np.asarray(tiled_segment_reduce(
            jnp.asarray(msgs_ch[p]), lay, jnp.asarray(lay.chunk_start[p]),
            jnp.asarray(lay.last_chunk[p]), jnp.asarray(lay.rel_dst[p]),
            sg.vpad, "min"))
        want = _oracle(msgs[p], sg, p, "min")
        np.testing.assert_array_equal(got, want)


def test_rejects_wide_tiles():
    """rel_dst is int8 (lane offsets 0..127, -1 pad): W > 128 would
    wrap offsets negative and silently drop edges (ADVICE r3)."""
    import pytest
    from lux_tpu.graph import Graph, ShardedGraph
    from lux_tpu.ops.tiled import TiledLayout

    rng = np.random.default_rng(3)
    g = Graph.from_edges(rng.integers(0, 300, 2000),
                         rng.integers(0, 300, 2000), 300)
    sg = ShardedGraph.build(g, 2)
    with pytest.raises(ValueError, match="W=256 > 128"):
        TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                          W=256, E=64)


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("trail", [(), (5,)])
def test_blocked_segscan_matches_monolithic(kind, trail):
    """_segscan_blocked must equal the monolithic associative scan for
    every reduce kind, ragged segment patterns, block-boundary
    straddles, and vector payloads — it replaces the scan whose
    O(log C) tree OOMs 16 GB chips at C ~ 1.4M (PERF_NOTES r4)."""
    from lux_tpu.ops.tiled import _segscan, _segscan_blocked

    rng = np.random.default_rng(11)
    C = 300                                   # not a block multiple
    vals = jnp.asarray(rng.random((C,) + trail).astype(np.float32))
    flags = rng.random(C) < 0.07              # long segments straddle
    flags[0] = True
    fl = jnp.asarray(flags)
    fb = fl.reshape((C,) + (1,) * len(trail))
    want = np.asarray(_segscan(vals, fb, kind))
    for block in (7, 64, 512):
        got = np.asarray(_segscan_blocked(vals, fl, kind, block=block))
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_combine_chunks_blocked_engages(monkeypatch):
    """Above the threshold the engine output is unchanged."""
    import lux_tpu.ops.tiled as tiled
    from lux_tpu.apps import pagerank
    from lux_tpu.graph import Graph

    rng = np.random.default_rng(5)
    nv, ne = 700, 30000
    src = (rng.zipf(1.3, ne) - 1) % nv
    dst = (rng.zipf(1.2, ne) - 1) % nv
    g = Graph.from_edges(src.astype(np.uint32), dst.astype(np.uint32),
                         nv)
    want = pagerank.run(g, 6)
    monkeypatch.setattr(tiled, "SCAN_BLOCKED_ABOVE", 4)
    monkeypatch.setattr(tiled, "SCAN_BLOCK_CHUNKS", 8)
    got = pagerank.run(g, 6)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # and through the owner exchange (the config that OOM'd)
    eng = pagerank.build_engine(g, num_parts=4, exchange="owner",
                                owner_tile_e=8)
    got_o = eng.unpad(eng.run(eng.init_state(), 6))
    np.testing.assert_allclose(got_o, want, rtol=1e-6)


# -- the lane-aligned placement (build(aligned=True)) ---------------------

def _in_degree_graph(degrees, seed=0):
    """A graph whose vertex ``v`` has in-degree ``degrees[v]`` (sources
    drawn at random, so a destination's in-edges are in no order)."""
    degrees = np.asarray(degrees, np.int64)
    nv = len(degrees)
    dst = np.repeat(np.arange(nv, dtype=np.uint32), degrees)
    src = np.random.default_rng(seed).integers(
        0, nv, len(dst)).astype(np.uint32)
    return Graph.from_edges(src, dst, nv)


def _aligned_graphs():
    rng = np.random.default_rng(7)
    skew = rng.zipf(1.6, 700).clip(max=900)
    skew[rng.random(700) < 0.45] = 0        # tiles without in-edges
    return {
        # one tile of hubs whose depths differ widely, the rest even
        "one-hub-tile": np.concatenate(
            [rng.integers(1, 400, 100), np.full(500, 6)]),
        "power-law-with-empty-tiles": skew,
        # vpad (8-aligned) is no multiple of the 128-wide tile
        "vpad-no-multiple-of-128": rng.integers(0, 9, 203),
        # every tile's depths differ by more than the constant allows
        "no-tile-aligned": np.tile(np.r_[200, np.zeros(127, int)], 3),
        # equal depths everywhere: every tile aligned, fill 1.0
        "all-tiles-aligned": np.full(512, 5),
    }


ALIGNED_GRAPHS = _aligned_graphs()


def _aligned_case(name, num_parts):
    sg = ShardedGraph.build(_in_degree_graph(ALIGNED_GRAPHS[name]),
                            num_parts)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            aligned=True)
    return sg, lay


def _reduce_part(lay, msgs_ch, p, vpad, kind, **kw):
    rank = None if lay.tile_rank is None else jnp.asarray(lay.tile_rank[p])
    return np.asarray(tiled_segment_reduce(
        jnp.asarray(msgs_ch[p]), lay, jnp.asarray(lay.chunk_start[p]),
        jnp.asarray(lay.last_chunk[p]), jnp.asarray(lay.rel_dst[p]),
        vpad, kind, tile_rank=rank, **kw))


@pytest.mark.parametrize("num_parts", [1, 2])
@pytest.mark.parametrize("name", list(ALIGNED_GRAPHS))
def test_aligned_places_every_edge_once(name, num_parts):
    """The aligned layout is a permutation of a part's edges: every
    edge in exactly one live slot, in the lane of its own destination
    (aligned chunks: slot e is lane e mod 128), a destination's
    in-edges in their stored order down the depth axis."""
    sg, lay = _aligned_case(name, num_parts)
    C, Ca, E, W = lay.n_chunks, lay.n_aligned, lay.E, lay.W
    assert Ca % 8 == 0 and (C - Ca) % 8 == 0 and C > 0
    for p in range(sg.num_parts):
        live = lay.rel_dst[p] >= 0
        ne_p = int(sg.row_ptr_local[p][-1])
        assert np.array_equal(np.sort(lay.edge_gather[p][live]),
                              np.arange(ne_p))
        # chunk_tile x W + rel_dst is the destination's RANK
        rank = (lay.chunk_tile[p].astype(np.int64)[:, None] * W
                + lay.rel_dst[p])[live]
        dst = sg.dst_local[p][lay.edge_gather[p][live]]
        assert np.array_equal(lay.tile_vertex[p][rank], dst)
        assert np.array_equal(lay.tile_rank[p][lay.tile_vertex[p]],
                              np.arange(sg.vpad))
        deg = np.diff(sg.row_ptr_local[p].astype(np.int64))
        assert np.all(np.diff(deg[lay.tile_vertex[p]]) <= 0)
        al = lay.rel_dst[p, :Ca]
        lanes = np.broadcast_to(np.arange(E) % W, al.shape)
        assert np.array_equal(al[al >= 0], lanes[al >= 0])
        # down a lane of an aligned tile the edge ids ascend by one
        eg = lay.edge_gather[p, :Ca].reshape(Ca, E // W, W)
        ok = (al.reshape(Ca, E // W, W) >= 0)
        both = ok[:, 1:] & ok[:, :-1]
        assert np.all((eg[:, 1:] - eg[:, :-1])[both] == 1)
    counts = lay.counts()
    assert counts["tiled_edges"] == sg.ne
    assert counts["tiled_slots"] == sg.num_parts * C * E
    assert counts["aligned_slots"] == sg.num_parts * Ca * E
    if name == "no-tile-aligned":
        assert Ca == 0 and counts["aligned_edges"] == 0
    if name == "all-tiles-aligned":
        assert Ca == C and counts["aligned_edges"] == sg.ne
    if name == "one-hub-tile" and num_parts == 1:
        assert 0 < Ca < C               # the hubs keep one-hot chunks


@pytest.mark.parametrize("trail", [(), (4,)], ids=["scalar", "vector"])
@pytest.mark.parametrize("kind", ["min", "max", "sum"])
@pytest.mark.parametrize("num_parts", [1, 2])
@pytest.mark.parametrize("name", list(ALIGNED_GRAPHS))
def test_aligned_matches_scatter_oracle(name, num_parts, kind, trail):
    """min / max bitwise, sum to float32 reassociation, against the
    flat segment_reduce; scalar and [.., K] payloads; and where no
    tile is aligned the answers are the default layout's."""
    sg, lay = _aligned_case(name, num_parts)
    rng = np.random.default_rng(11)
    shape = (sg.num_parts, sg.epad) + trail
    if kind == "sum":
        msgs = rng.random(shape).astype(np.float32)
        ident = np.float32(0)
    else:
        msgs = rng.integers(-1000, 1000, shape).astype(np.int32)
        info = np.iinfo(np.int32)
        ident = info.max if kind == "min" else info.min
    pad = (sg.dst_local >= sg.vpad).reshape(shape[:2] + (1,) * len(trail))
    msgs = np.where(pad, ident, msgs).astype(msgs.dtype)
    msgs_ch = lay.chunk(msgs)
    plain = None
    if name == "no-tile-aligned":
        plain = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad)
    for p in range(sg.num_parts):
        got = _reduce_part(lay, msgs_ch, p, sg.vpad, kind)
        want = _oracle(msgs[p], sg, p, kind)
        if kind == "sum":
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)
        if plain is not None:       # a sum's terms come in rank order
            same = _reduce_part(plain, plain.chunk(msgs), p, sg.vpad,
                                kind)
            np.testing.assert_allclose(got, same, rtol=1e-5 * (kind == "sum"))


@pytest.mark.parametrize("method", ["mxu", "pallas-interpret",
                                    "streamed"])
@pytest.mark.parametrize("kind", ["min", "sum"])
def test_aligned_under_every_reduce_method(kind, method):
    """The fold over depth beside each formulation of the one-hot
    chunks and of the combine: the MXU contraction (which an aligned
    chunk has nothing for), the Pallas kernels, the streamed blocks."""
    from lux_tpu.ops.tiled import (combine_partials,
                                   streamed_chunk_partials)
    sg, lay = _aligned_case("one-hub-tile", 1)
    assert 0 < lay.n_aligned < lay.n_chunks
    rng = np.random.default_rng(5)
    state = rng.integers(0, 1000, (sg.num_parts * sg.vpad, 4)).astype(
        np.float32 if kind == "sum" else np.int32)
    msgs = state[sg.src_slot]                            # [P, epad, 4]
    live = (sg.dst_local < sg.vpad)[..., None]
    ident = 0 if kind == "sum" else np.iinfo(np.int32).max
    msgs = np.where(live, msgs, ident).astype(state.dtype)
    want = _oracle(msgs[0], sg, 0, kind)
    if method == "streamed":
        partials = streamed_chunk_partials(
            jnp.asarray(state), jnp.asarray(lay.chunk(sg.src_slot)[0]),
            jnp.asarray(lay.rel_dst[0]), None, lay, kind,
            lambda v, w: v, "xla", block_chunks=8)
        got = np.asarray(combine_partials(
            partials, lay, jnp.asarray(lay.chunk_start[0]),
            jnp.asarray(lay.last_chunk[0]), sg.vpad, kind,
            tile_rank=jnp.asarray(lay.tile_rank[0])))
    else:
        kw = (dict(use_mxu=True) if method == "mxu" else
              dict(method="pallas", interpret=True))
        got = _reduce_part(lay, lay.chunk(msgs), 0, sg.vpad, kind, **kw)
    np.testing.assert_array_equal(got, want)


def test_aligned_fill_constant_decides_a_tile(monkeypatch):
    """A tile whose edges fill 60% of its 128 x depth aligned slots is
    aligned at the module's constant and one-hot above it; chunks
    shorter than a depth row are refused."""
    from lux_tpu.ops import tiled
    assert tiled.ALIGNED_MIN_FILL == 0.55
    deg = np.r_[np.full(28, 100), np.full(100, 49)]     # fill 0.602
    sg = ShardedGraph.build(_in_degree_graph(deg), 1)
    def build(**kw):
        return TiledLayout.build(sg.row_ptr_local, sg.dst_local,
                                 sg.vpad, aligned=True, **kw)

    lay = build()
    assert lay.n_aligned == lay.n_chunks == 32          # ceil(100 / 4) -> 8s
    monkeypatch.setattr(tiled, "ALIGNED_MIN_FILL", 0.65)
    lay = build()
    assert lay.n_aligned == 0 and lay.n_chunks == 16    # 7,700 edges / 512
    with pytest.raises(ValueError, match="whole depth rows"):
        build(E=64)


def test_default_layout_is_untouched_by_the_aligned_fields():
    sg = _sharded(300, 2500, 2)
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad)
    assert (lay.n_aligned, lay.tile_rank, lay.tile_vertex) == (0, None,
                                                               None)
    counts = lay.counts()
    assert counts["aligned_edges"] == counts["aligned_slots"] == 0
    assert counts["tiled_edges"] == sg.ne
