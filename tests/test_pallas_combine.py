"""The Pallas chunk-combine kernel (ops/pallas_combine.py), exercised
off-TPU in interpret mode, against the flag-reset associative scan it
replaces on the ``pallas`` reduce method (ops/tiled._segscan): every
reduce kind x payload shape x flag pattern, under ``vmap`` over parts
and under ``shard_map``, and through both engines
(reduce_method='pallas-interpret' against 'xla')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu.ops.pallas_combine import (row_block,
                                        segmented_combine_pallas)
from lux_tpu.ops.tiled import _segscan

# (trailing payload shape, chunks per grid step): the scalar payload
# runs the 8-chunk tile kernel, the vector payloads the row kernel
PAYLOADS = {"scalar": ((128,), 1024),
            "k16": ((16, 128), 128),
            "k20": ((20, 128), 128)}
C = 2 * 1024 + 1024 // 2 + 3        # 2563: no multiple of 8, 128, 1024


def _flags(pattern: str, block: int) -> np.ndarray:
    fl = np.zeros(C, bool)
    if pattern == "every-chunk":
        fl[:] = True
    elif pattern == "spans-blocks":
        # one segment from chunk 5 across every later grid block: the
        # carry crosses (and chunks 0..4 lead without a flag)
        fl[5] = True
    elif pattern == "ends-on-block-edge":
        # segments that end on a block's last row / start on its first
        fl[[0, block - 3, block, 2 * block - 1, 2 * block]] = True
    elif pattern == "trailing-pads":
        # the layout's pad chunks: isolated segments at the end
        fl[np.sort(np.random.default_rng(5).choice(
            C - 40, 300, replace=False))] = True
        fl[C - 40:] = True
    else:
        # random, every run length 1..8 inside one [8, 128] tile
        fl[:] = np.random.default_rng(6).random(C) < 0.35
    return fl


def _payload(trail, dtype, pattern, kind):
    rng = np.random.default_rng(7)
    x = rng.random((C,) + trail) + 0.5
    if np.dtype(dtype).kind == "i":
        x = x * 1000
    x = x.astype(dtype)
    if pattern == "trailing-pads":
        from lux_tpu.ops.segment import identity_for
        x[C - 40:] = identity_for(kind, np.dtype(dtype))
    return x


def _check(got, want, kind, dtype, longest=1):
    if kind == "sum" and np.dtype(dtype).kind == "f":
        # the same terms, left to right where the tree paired them: a
        # segment of a thousand terms rounds a thousand times
        np.testing.assert_allclose(
            got, want, rtol=1e-6 if longest <= 64 else 1e-5)
    else:
        np.testing.assert_array_equal(got, want)       # bitwise


@pytest.mark.parametrize("pattern", [
    "every-chunk", "spans-blocks", "ends-on-block-edge",
    "trailing-pads", "random"])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("payload", list(PAYLOADS))
def test_kernel_equals_the_associative_scan(payload, kind, dtype,
                                            pattern):
    trail, block = PAYLOADS[payload]
    fl = _flags(pattern, block)
    x = _payload(trail, dtype, pattern, kind)
    got = np.asarray(segmented_combine_pallas(
        jnp.asarray(x), jnp.asarray(fl), kind, block_c=block,
        interpret=True))
    fl[0] = True        # chunk 0 starts a segment whatever its flag
    want = np.asarray(_segscan(
        jnp.asarray(x),
        jnp.asarray(fl).reshape((C,) + (1,) * len(trail)), kind))
    _check(got, want, kind, dtype,
           longest=np.diff(np.flatnonzero(np.r_[fl, True])).max())


@pytest.mark.parametrize("payload", list(PAYLOADS))
def test_default_block_follows_the_payload_shape(payload):
    """No block_c: the block comes from (R, C); a short chunk axis is
    one block."""
    trail, _ = PAYLOADS[payload]
    fl = _flags("random", 128)[:300]
    x = _payload(trail, np.float32, "random", "min")[:300]
    got = np.asarray(segmented_combine_pallas(
        jnp.asarray(x), jnp.asarray(fl), "min", interpret=True))
    fl[0] = True
    want = np.asarray(_segscan(
        jnp.asarray(x),
        jnp.asarray(fl).reshape((300,) + (1,) * len(trail)), "min"))
    np.testing.assert_array_equal(got, want)


def test_row_block_keeps_the_block_within_its_bytes():
    from lux_tpu.ops.pallas_combine import (MAX_ROWS, ROW_BLOCK_BYTES,
                                            ROW_UNROLL, kernel_takes)
    for R in (2, 8, 16, 20, 64, 128, MAX_ROWS):
        bc = row_block(R)
        assert bc >= ROW_UNROLL and bc % ROW_UNROLL == 0
        assert bc * (-(-R // 8) * 8) * 128 * 4 <= ROW_BLOCK_BYTES
        assert kernel_takes((9, R, 128), np.float32)
    assert row_block(16) == 256
    assert not kernel_takes((9, MAX_ROWS + 1, 128), np.float32)


def test_kernel_refuses_what_it_cannot_lay_out():
    fl = jnp.ones(16, bool)
    for shape, dtype in (((16, 64), jnp.float32),       # W != 128
                         ((16, 128), jnp.bfloat16),     # 2-byte
                         ((16, 2, 3, 128), jnp.float32)):
        with pytest.raises(ValueError, match="segmented_combine"):
            segmented_combine_pallas(jnp.zeros(shape, dtype), fl,
                                     "sum", interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        segmented_combine_pallas(jnp.zeros((16, 128)), fl, "sum",
                                 block_c=100, interpret=True)


@pytest.mark.parametrize("payload", ["scalar", "k16"])
def test_kernel_under_vmap_over_parts(payload):
    """The engines' position: ``vmap`` over a leading parts axis.
    Part 1's chunk 0 carries no flag, so a carry that leaked over
    from part 0's last chunk would show."""
    trail, block = PAYLOADS[payload]
    P = 3
    rng = np.random.default_rng(8)
    x = (rng.random((P, C) + trail) * 1000).astype(np.int32)
    fl = rng.random((P, C)) < 0.2
    fl[:, 0] = [True, False, False]
    got = np.asarray(jax.vmap(lambda v, f: segmented_combine_pallas(
        v, f, "sum", block_c=block, interpret=True))(
            jnp.asarray(x), jnp.asarray(fl)))
    fl[:, 0] = True
    for p in range(P):
        want = np.asarray(_segscan(
            jnp.asarray(x[p]),
            jnp.asarray(fl[p]).reshape((C,) + (1,) * len(trail)),
            "sum"))
        np.testing.assert_array_equal(got[p], want)


def test_kernel_under_shard_map_states_its_vma():
    """ops/owner.py calls combine_chunks under the VMA-checked
    ``shard_map``: the kernel's out_shape must say over which mesh
    axes its result varies, or the trace is refused.  jax 0.9's
    Pallas interpreter cannot EVALUATE a kernel body under that check
    (its literals are not typed varying; the same holds for
    chunk_partials_pallas), so the typed path is traced here and
    compiled for the chip in tests/test_tpu_compile.py, and the
    values are checked with the check off."""
    from jax.sharding import Mesh, PartitionSpec as PS

    from lux_tpu.parallel.mesh import PARTS_AXIS
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), (PARTS_AXIS,))
    P, Cs = len(devs), 300
    rng = np.random.default_rng(9)
    x = jnp.asarray((rng.random((P, Cs, 128)) * 1000).astype(np.int32))
    fl = rng.random((P, Cs)) < 0.3
    fl[:, 0] = True
    seen = []

    def per_device(interpret, v, f):
        out = segmented_combine_pallas(v[0], f[0], "min",
                                       interpret=interpret)
        seen.append(jax.typeof(out).vma)
        return out[None]

    def on_mesh(interpret, **kw):
        import functools
        return jax.shard_map(
            functools.partial(per_device, interpret), mesh=mesh,
            in_specs=PS(PARTS_AXIS), out_specs=PS(PARTS_AXIS), **kw)

    jax.make_jaxpr(on_mesh(False))(x, jnp.asarray(fl))
    assert seen == [frozenset({PARTS_AXIS})]
    got = np.asarray(jax.jit(on_mesh(True, check_vma=False))(
        x, jnp.asarray(fl)))
    for p in range(P):
        want = np.asarray(_segscan(x[p], jnp.asarray(fl[p])[:, None],
                                   "min"))
        np.testing.assert_array_equal(got[p], want)


# -- combine_chunks: when the kernel engages -----------------------------

def _layout(Cn, n_tiles, seed):
    """A TiledLayout's combine-relevant fields for Cn chunks in
    n_tiles tiles (every tile at least one chunk)."""
    from lux_tpu.ops.tiled import TiledLayout
    rng = np.random.default_rng(seed)
    cut = np.sort(rng.choice(np.arange(1, Cn), n_tiles - 1,
                             replace=False))
    start = np.zeros(Cn, bool)
    start[np.r_[0, cut]] = True
    last = np.r_[cut - 1, Cn - 1].astype(np.int32)
    last[3] = -1                        # an edge-less tile
    lay = TiledLayout(W=128, E=8, n_tiles=n_tiles, n_chunks=Cn,
                      needs_scan=True, edge_gather=None, rel_dst=None,
                      chunk_tile=None, chunk_start=start,
                      last_chunk=last)
    return lay, jnp.asarray(start), jnp.asarray(last)


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("trail", [(), (16,), (20,)],
                         ids=["scalar", "k16", "k20"])
def test_combine_chunks_kernel_matches_scan(trail, kind):
    """combine_chunks under method='pallas' (interpret) against
    method='xla': the same tile rows [n_tiles, W, ...], the vector
    payload combined in the lane-minor order and moved after the
    last-chunk take."""
    from lux_tpu.ops.tiled import combine_chunks
    Cn, n_tiles = 200, 37
    lay, start, last = _layout(Cn, n_tiles, 10)
    rng = np.random.default_rng(11)
    lane_minor = bool(trail)
    x = jnp.asarray((rng.random((Cn,) + trail + (128,)) * 1000)
                    .astype(np.int32))
    want = combine_chunks(x, lay, start, last, kind,
                          lane_minor=lane_minor)
    got = combine_chunks(x, lay, start, last, kind, method="pallas",
                         interpret=True, lane_minor=lane_minor)
    assert got.shape == (n_tiles, 128) + trail
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if trail:
        # and both equal the [C, W, K] order the MXU path keeps
        std = combine_chunks(jnp.moveaxis(x, -1, 1), lay, start, last,
                             kind)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(std))


@pytest.mark.parametrize("case", ["xla", "w-major-vector", "bfloat16",
                                  "mxu-sum", "pallas-scalar",
                                  "pallas-lane-minor"])
def test_combine_chunks_engages_by_method_and_shape(case):
    """The kernel follows the resolved method and the payload's
    shape, nothing else: ``xla`` never runs it; ``pallas`` runs it for
    4-byte [C, 128] and lane-minor [C, K, 128] payloads and leaves
    [C, W, K] vectors, narrow dtypes and the MXU sum on their scans."""
    from lux_tpu.ops.tiled import combine_chunks
    lay, start, last = _layout(64, 9, 12)
    kw = dict(method="pallas", interpret=True)
    x = jnp.ones((64, 128), jnp.float32)
    v = jnp.ones((64, 16, 128), jnp.float32)
    args, expect = {
        "xla": ((x, "min", {}), False),
        "w-major-vector": ((v, "min", kw), False),
        "bfloat16": ((x.astype(jnp.bfloat16), "min", kw), False),
        "mxu-sum": ((x, "sum", dict(kw, use_mxu=True)), False),
        "pallas-scalar": ((x, "min", kw), True),
        "pallas-lane-minor": ((v, "min", dict(kw, lane_minor=True)),
                              True),
    }[case]
    p, kind, kwargs = args
    jaxpr = str(jax.make_jaxpr(lambda a: combine_chunks(
        a, lay, start, last, kind, **kwargs))(p))
    assert ("pallas_call" in jaxpr) == expect
    # one scope names the combine whatever runs in it
    text = jax.jit(lambda a: combine_chunks(
        a, lay, start, last, kind, **kwargs)).lower(p).as_text(
            debug_info=True)
    assert "lux_combine" in text


# -- engine level --------------------------------------------------------

@pytest.fixture(scope="module")
def rmat10():
    from lux_tpu.convert import rmat_graph
    return rmat_graph(scale=10, edge_factor=16, seed=3)


def test_batched_push_engine_bitwise_equal_under_the_kernel(rmat10):
    """k-source hop distances, B = 4, tile_e = 128 so tiles span
    chunks: 'min' answers and iteration counts bitwise equal; built
    with audit='error', so the gather budget, dtype discipline and
    constant-bytes audits walk the program with the kernel in."""
    from lux_tpu.apps import sssp
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import ShardedGraph
    sg = ShardedGraph.build(rmat10, 2)
    sources = [int(v) for v in
               np.flatnonzero(rmat10.out_degrees)[:4]]
    out = {}
    for method in ("xla", "pallas-interpret"):
        eng = PushEngine(sg, sssp.make_batched_program(sources, False),
                         reduce_method=method, tile_e=128,
                         audit="error")
        assert eng.delivery.tiles.needs_scan
        out[method] = eng.run()
    assert out["xla"][1] == out["pallas-interpret"][1] > 2
    np.testing.assert_array_equal(out["xla"][0],
                                  out["pallas-interpret"][0])


@pytest.mark.parametrize("num_parts", [1, 2])
def test_pull_engine_equal_under_the_kernel(rmat10, num_parts):
    """PageRank ('sum', scalar payload: the 8-chunk tile kernel behind
    the partial kernel), audited, against the xla method."""
    from lux_tpu.apps import pagerank
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.graph import ShardedGraph
    sg = ShardedGraph.build(rmat10, num_parts)
    out = {}
    for method in ("xla", "pallas-interpret"):
        eng = PullEngine(sg, pagerank.make_program(),
                         reduce_method=method, tile_e=128,
                         audit="error")
        assert eng.delivery.tiles.needs_scan
        out[method] = eng.unpad(eng.run(eng.init_state(), 6))
    np.testing.assert_allclose(out["pallas-interpret"], out["xla"],
                               rtol=1e-6)
