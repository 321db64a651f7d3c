"""MXU compute core (round 23): bitwise MXU-vs-VPU oracles.

ops/tiled.py's one-hot contraction reduce (sum einsum + the
bit-serial compare tournament), the segmented-scan matmul, the
frontier cumsum-as-matmul (engine/frontier.py), the engine-level A/B
across kinds x payload widths x meshes x delivery modes (the swap
must be INVISIBLE: bitwise for integer states, reassociation-
tolerance for float sums), the typed unsupported error, the
``use_mxu="auto"`` break-even resolution (lux_tpu/scalemodel.py) and
the ``mxu_temp`` ledger term (graph.memory_report).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu.apps import colfilter, components, pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.engine import frontier as fr
from lux_tpu.graph import Graph
from lux_tpu.ops.segment import identity_for, segment_reduce
from lux_tpu.ops.tiled import (MXUUnsupportedError, _order_decode,
                               _order_encode, _segscan,
                               _segscan_matmul, chunk_partials)
from lux_tpu.parallel.mesh import make_mesh

NV, NE = 256, 2048
SOURCES = [0, 5, 9, 100, 131, 7, 200, 63]        # B = 8


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


@pytest.fixture(scope="module")
def g():
    src, dst = uniform_random_edges(NV, NE, seed=3)
    return Graph.from_edges(src, dst, NV)


@pytest.fixture(scope="module")
def gw():
    rng = np.random.default_rng(4)
    src, dst = uniform_random_edges(NV, NE, seed=4)
    return Graph.from_edges(src, dst, NV,
                            weights=rng.integers(1, 6, NE).astype(
                                np.float32))


# ---------------------------------------------------------------------
# ops level: chunk_partials MXU vs VPU, every kind x dtype x payload


def _rand_chunks(dtype, trail=(), seed=0, C=6, E=96, W=128):
    """Random [C, E(, K)] payload + rel_dst with ~15% pad lanes, one
    all-pad chunk (its slots must come back as the identity) and
    garbage payload values AT the pads (the contract: pads contribute
    the identity regardless of what the lanes carry)."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if dt.kind == "f":
        vals = (rng.standard_normal((C, E) + trail) * 100).astype(dt)
    else:
        info = np.iinfo(dt)
        vals = rng.integers(info.min, int(info.max) + 1,
                            (C, E) + trail, dtype=np.int64).astype(dt)
    rel = rng.integers(0, W, (C, E)).astype(np.int8)
    rel[rng.random((C, E)) < 0.15] = -1
    rel[C // 2] = -1
    return jnp.asarray(vals), jnp.asarray(rel)


COMPARE_DTYPES = [np.int32, np.int16, np.int8, np.uint32, np.uint16,
                  np.uint8, np.float32, np.float16]


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("dtype", COMPARE_DTYPES)
@pytest.mark.parametrize("trail", [(), (3,)])
def test_compare_reduce_bitwise(kind, dtype, trail):
    """The tournament is BITWISE-equal to the VPU masked reduce for
    every supported dtype — floats included (the order encoding is a
    total order, so there is no reassociation to diverge on)."""
    vals, rel = _rand_chunks(dtype, trail)
    want = np.asarray(chunk_partials(vals, rel, 128, kind))
    got = np.asarray(chunk_partials(vals, rel, 128, kind,
                                    use_mxu=True))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("trail", [(), (3,)])
def test_sum_contraction_bitwise_int(dtype, trail):
    vals, rel = _rand_chunks(dtype, trail, seed=1)
    want = np.asarray(chunk_partials(vals, rel, 128, "sum"))
    got = np.asarray(chunk_partials(vals, rel, 128, "sum",
                                    use_mxu=True))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("trail", [(), (5,)])
def test_sum_contraction_float_tolerance(trail):
    """Float sums reassociate under the contraction — tolerance, not
    bitwise, is the float-sum contract (same as the engines')."""
    vals, rel = _rand_chunks(np.float32, trail, seed=2)
    want = np.asarray(chunk_partials(vals, rel, 128, "sum"))
    got = np.asarray(chunk_partials(vals, rel, 128, "sum",
                                    use_mxu=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def test_all_pad_chunk_is_identity():
    for kind in ("sum", "min", "max"):
        vals, _ = _rand_chunks(np.int32)
        rel = jnp.full((6, 96), -1, jnp.int8)
        out = np.asarray(chunk_partials(vals, rel, 128, kind,
                                        use_mxu=True))
        ident = identity_for(kind, jnp.int32)
        np.testing.assert_array_equal(
            out, np.full((6, 128), np.asarray(ident), np.int32))


def test_order_encode_roundtrip_and_monotone():
    rng = np.random.default_rng(9)
    for dt in COMPARE_DTYPES:
        dt = np.dtype(dt)
        if dt.kind == "f":
            x = np.sort((rng.standard_normal(64) * 50).astype(dt))
            x = np.concatenate(([-np.inf], x, [np.inf])).astype(dt)
        else:
            info = np.iinfo(dt)
            x = np.sort(rng.integers(info.min, int(info.max) + 1, 64,
                                     dtype=np.int64)).astype(dt)
        enc = np.asarray(_order_encode(jnp.asarray(x)))
        assert enc.dtype == np.uint32
        # unsigned order == payload order, decode inverts
        assert (np.diff(enc.astype(np.uint64)) >= 0).all(), dt
        np.testing.assert_array_equal(
            np.asarray(_order_decode(jnp.asarray(enc), dt)), x)


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_unsupported_dtype_raises_typed(kind):
    vals = jnp.zeros((2, 8, 2), jnp.complex64)
    rel = jnp.zeros((2, 8), jnp.int8)
    with pytest.raises(MXUUnsupportedError) as ei:
        chunk_partials(vals, rel, 128, kind, use_mxu=True)
    # the error names the kind and dtype so the fallback is deliberate
    assert "complex64" in str(ei.value)
    assert ei.value.dtype == np.dtype(np.complex64)


# ---------------------------------------------------------------------
# segmented combine: the scan-as-matmul block recurrence


@pytest.mark.parametrize("trail", [(), (4,)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_segscan_matmul_matches_vpu_scan(trail, dtype):
    rng = np.random.default_rng(11)
    C = 300                                   # not a block multiple
    if np.dtype(dtype).kind == "f":
        vals = rng.random((C,) + trail).astype(dtype)
    else:
        vals = rng.integers(-1000, 1000, (C,) + trail).astype(dtype)
    flags = rng.random(C) < 0.07              # segments straddle blocks
    flags[0] = True
    fl = jnp.asarray(flags)
    fb = fl.reshape((C,) + (1,) * len(trail))
    want = np.asarray(_segscan(jnp.asarray(vals), fb, "sum"))
    for block in (7, 64, 512):
        got = np.asarray(_segscan_matmul(jnp.asarray(vals), fl,
                                         block=block))
        if np.dtype(dtype).kind == "f":
            np.testing.assert_allclose(got, want, rtol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)


def test_segscan_matmul_no_leading_flag():
    """A block whose first chunk continues a straddling segment must
    absorb the carry (the sid==0 absorb lane)."""
    vals = jnp.asarray(np.arange(1, 9, dtype=np.int32))
    fl = jnp.asarray(np.array([1, 0, 0, 0, 0, 1, 0, 0], bool))
    want = np.asarray(_segscan(vals, fl, "sum"))
    got = np.asarray(_segscan_matmul(vals, fl, block=3))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------
# frontier: scatter-max/cummax as scatter-add/cumsum-matmul


def test_cumsum_matmul_bitwise():
    rng = np.random.default_rng(5)
    for n in (1, 7, 256, 1000):
        x = jnp.asarray(rng.integers(0, 100, n).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(fr._cumsum_matmul(x, block=64)),
            np.cumsum(np.asarray(x), dtype=np.int32))


@pytest.mark.parametrize("seed", range(8))
def test_expand_frontier_mxu_bitwise(seed):
    """The MXU edge-slot expansion is bitwise-equal to the VPU
    scatter-max/cummax across randomized queues, truncation and
    degree-0 sources."""
    rng = np.random.default_rng(seed)
    nv = 40
    deg = rng.integers(0, 6, nv)
    deg[rng.random(nv) < 0.3] = 0
    rp = np.concatenate(([0], np.cumsum(deg)))
    present = np.nonzero(deg > 0)[0]
    off = np.concatenate(([0], np.cumsum(deg[present])))
    sids = jnp.asarray(present.astype(np.int32))
    soff = jnp.asarray(off.astype(np.int32))
    q = rng.integers(1, 9)
    ids_np = np.full(q, nv, np.int32)
    k = rng.integers(0, q + 1)
    if k:
        ids_np[:k] = rng.choice(nv, size=k, replace=False)
    ids = jnp.asarray(ids_np)
    vals = jnp.asarray(rng.integers(0, 100, q).astype(np.int32))
    budget = int(rng.integers(1, int(rp[-1]) + 4))
    out_v = fr.expand_frontier(ids, vals, sids, soff, nv, budget)
    out_m = fr.expand_frontier(ids, vals, sids, soff, nv, budget,
                               use_mxu=True)
    for a, b in zip(out_v, out_m):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------
# engine level: the A/B must be invisible in the answers


def _ab(build):
    em, ev = build(True), build(False)
    assert em.use_mxu is True and ev.use_mxu is False
    return em, ev


@pytest.mark.parametrize("gather", ["flat", "paged", "pagemajor"])
def test_pagerank_delivery_modes(g, gather):
    """Scalar f32 sum across delivery modes: the reduce swap is
    tolerance-invisible and the oracle still holds."""
    em, ev = _ab(lambda um: pagerank.build_engine(
        g, num_parts=2, gather=gather, use_mxu=um))
    got_m = em.unpad(em.run(em.init_state(), 5))
    got_v = ev.unpad(ev.run(ev.init_state(), 5))
    np.testing.assert_allclose(got_m, got_v, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(got_m, pagerank.reference_pagerank(g, 5),
                               rtol=1e-4, atol=1e-7)


def test_ppr_batched_auto_engages_and_matches(g):
    """The flagship auto-engagement: B=8 batched personalized
    pagerank resolves use_mxu=True from the scalemodel break-even
    (wide 8 >= 2) and matches the forced-VPU build and the oracle."""
    em = pagerank.build_engine(g, num_parts=2, sources=SOURCES)
    assert em.use_mxu is True
    ev = pagerank.build_engine(g, num_parts=2, sources=SOURCES,
                               use_mxu=False)
    got_m = em.unpad(em.run(em.init_state(), 6))
    got_v = ev.unpad(ev.run(ev.init_state(), 6))
    np.testing.assert_allclose(got_m, got_v, rtol=1e-5, atol=1e-8)
    resets = pagerank.one_hot_resets(g.nv, SOURCES)
    np.testing.assert_allclose(
        got_m, pagerank.reference_pagerank_batched(g, resets, 6),
        rtol=1e-4, atol=1e-7)


def test_colfilter_k20_auto_engages(gw):
    """K=20 vector payload (sum): wide 20 >= 2 auto-engages, and the
    factors match the forced-VPU run."""
    em = colfilter.build_engine(gw, num_parts=2)
    assert em.use_mxu is True
    ev = colfilter.build_engine(gw, num_parts=2, use_mxu=False)
    got_m = em.unpad(em.run(em.init_state(), 3))
    got_v = ev.unpad(ev.run(ev.init_state(), 3))
    np.testing.assert_allclose(got_m, got_v, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("np_parts,use_mesh", [(2, False), (8, True)])
def test_sssp_min_bitwise(g, mesh8, np_parts, use_mesh):
    """int32 min labels: the tournament swap is BITWISE-invisible,
    single-chip and on the 8-virtual-device mesh."""
    mesh = mesh8 if use_mesh else None
    em, ev = _ab(lambda um: sssp.build_engine(
        g, start_vertex=1, num_parts=np_parts, mesh=mesh,
        use_mxu=um))
    lm, _am, itm = em.converge(*em.init_state())
    lv, _av, itv = ev.converge(*ev.init_state())
    assert int(jax.device_get(itm)) == int(jax.device_get(itv))
    np.testing.assert_array_equal(np.asarray(jax.device_get(lm)),
                                  np.asarray(jax.device_get(lv)))
    np.testing.assert_array_equal(
        em.unpad(lm).astype(np.int64),
        np.where(sssp.reference_sssp(g, 1) >= int(sssp.HOP_INF),
                 int(sssp.HOP_INF), sssp.reference_sssp(g, 1)))


@pytest.mark.parametrize("exchange", ["gather", "owner"])
def test_components_max_bitwise(g, exchange):
    """max-label propagation through BOTH exchanges: bitwise."""
    em, ev = _ab(lambda um: components.build_engine(
        g, num_parts=2, exchange=exchange, use_mxu=um))
    lm = em.converge(*em.init_state())[0]
    lv = ev.converge(*ev.init_state())[0]
    np.testing.assert_array_equal(np.asarray(jax.device_get(lm)),
                                  np.asarray(jax.device_get(lv)))


def test_ksssp_batched_owner_mesh8_bitwise(g, mesh8):
    """B=8 k-source SSSP, owner exchange, mesh8: the full stack —
    batched tournament + owner-side combine + collectives — is
    bitwise-invisible and oracle-exact."""
    em, ev = _ab(lambda um: sssp.build_engine(
        g, sources=SOURCES, num_parts=8, mesh=mesh8,
        exchange="owner", use_mxu=um))
    lm = em.converge(*em.init_state())[0]
    lv = ev.converge(*ev.init_state())[0]
    np.testing.assert_array_equal(np.asarray(jax.device_get(lm)),
                                  np.asarray(jax.device_get(lv)))
    ref = sssp.reference_sssp_batched(g, SOURCES)
    np.testing.assert_array_equal(
        em.unpad(lm).astype(np.int64),
        np.where(ref >= int(sssp.HOP_INF), int(sssp.HOP_INF), ref))


def test_stats_counters_bitwise(g):
    """The stats loop variant: frontier/edge counters are exact
    integer series and must be BITWISE-equal across the swap."""
    em, ev = _ab(lambda um: sssp.build_engine(
        g, start_vertex=0, num_parts=2, use_mxu=um))
    lm, _a, itm, fszm, fedm, _fp, _ep = em.converge_stats(
        *em.init_state())
    lv, _a2, itv, fszv, fedv, _fp2, _ep2 = ev.converge_stats(
        *ev.init_state())
    it = int(jax.device_get(itm))
    assert it == int(jax.device_get(itv))
    np.testing.assert_array_equal(np.asarray(jax.device_get(lm)),
                                  np.asarray(jax.device_get(lv)))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(fszm))[:it],
        np.asarray(jax.device_get(fszv))[:it])
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(fedm))[:it],
        np.asarray(jax.device_get(fedv))[:it])


def test_health_variant_matches(g):
    """The health loop variant runs the MXU path clean and lands on
    the same labels as the plain VPU converge."""
    from lux_tpu import health as hw

    em = sssp.build_engine(g, start_vertex=1, num_parts=2,
                           use_mxu=True, health=True)
    lm = em.converge_health(*em.init_state())
    h = lm[-1]
    assert not hw.ensure_ok(h, engine="push")["tripped"]
    ev = sssp.build_engine(g, start_vertex=1, num_parts=2,
                           use_mxu=False)
    lv = ev.converge(*ev.init_state())[0]
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(lm[0])),
        np.asarray(jax.device_get(lv)))


# ---------------------------------------------------------------------
# auto resolution, scalemodel terms, ledger term, carried debt


def test_break_even_table():
    from lux_tpu import scalemodel as sm

    assert sm.mxu_break_even_wide("sum") == 2
    # 32-bit compare: 64 contraction rounds outrun the VPU margin at
    # every width — min/max NEVER auto-engage (honest negative)
    assert sm.mxu_break_even_wide("min") >= 1 << 30
    assert sm.mxu_break_even_wide("max") >= 1 << 30
    # 16-bit states halve the tournament; finite break-even
    assert sm.mxu_break_even_wide("max", nbits=16) == 3
    assert sm.resolve_use_mxu("sum", wide=2) is True
    assert sm.resolve_use_mxu("sum", wide=1) is False
    assert sm.resolve_use_mxu("min", wide=4096) is False
    with pytest.raises(ValueError):
        sm.mxu_reduce_rounds("prod")


def test_engine_auto_resolution(g, gw):
    """Scalar sum stays VPU (preserving the f32 flagships' bitwise
    behavior), wide payloads engage, min never auto-engages, and a
    bogus flag raises."""
    assert pagerank.build_engine(g, num_parts=2).use_mxu is False
    assert sssp.build_engine(g, num_parts=2).use_mxu is False
    assert pagerank.build_engine(
        g, num_parts=2, sources=SOURCES).use_mxu is True
    with pytest.raises(ValueError, match="use_mxu"):
        pagerank.build_engine(g, num_parts=2, use_mxu="fast")


def test_phase_model_prices_mxu_reduce():
    from lux_tpu import scalemodel as sm

    kw = dict(engine="pull", exchange="gather", ne=10**7, nv=10**5)
    vpu = sm.phase_model(**kw)
    mxu = sm.phase_model(**kw, use_mxu=True, mxu_wide=8)
    # the VPU reduce rides inside the fused gather figure (no
    # separate constant); with use_mxu the contraction IS modeled
    assert mxu["reduce"] is not None and mxu["reduce"] > 0
    rows = 10**7 * 1.2 / 128
    assert mxu["reduce"] == pytest.approx(
        rows * sm.mxu_reduce_row_ns(8, "sum"), rel=1e-9)
    assert vpu.get("reduce") in (None, 0)


def test_memory_report_mxu_temp(g):
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.ops.tiled import STREAM_BLOCK_CHUNKS

    sg = ShardedGraph.build(g, 2)
    rep = sg.memory_report()
    assert rep["mxu_temp_bytes_per_part"] == 0
    rep_m = sg.memory_report(use_mxu=True, mxu_tile_e=512)
    want = min(sg.epad, STREAM_BLOCK_CHUNKS * 512) * 128
    assert rep_m["mxu_temp_bytes_per_part"] == want
    assert rep_m["terms_per_part"]["mxu_temp"] == want
    assert (rep_m["total_bytes"] - rep["total_bytes"]
            == sg.num_parts * want)
