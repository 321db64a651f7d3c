"""Compile-only TPU v5e checks, runnable without a chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)``
hands back a compile-only v5e client from the installed libtpu, so the
Mosaic lowering of the Pallas kernels and the TPU compile of whole
engine programs are checked in tier-1 on the CPU box: a kernel that
stops lowering, or a block size that no longer fits scoped VMEM, fails
here instead of on the first chip run.  Compiling is not running —
``chip_smoke.py`` is what runs them.

Skips, with the reason printed, where the topology client cannot be
created (no libtpu in the environment).
"""

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    """The compile-only v5e 2x2 topology (four devices)."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any init failure = no client
        pytest.skip(f"no compile-only TPU topology client: "
                    f"{type(e).__name__}: {str(e)[:200]}")


@pytest.fixture(scope="module")
def v5e(topo):
    """SingleDeviceSharding on one compile-only v5e device."""
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Abstract stand-ins of ``tree``'s arrays, placed on ``sharding``
    (lowering against them targets that device's platform)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _abstract_shard(parts):
    """A stand-in for ``shard_over_parts`` on a described topology,
    whose devices cannot hold data: abstract arrays on ``parts``."""
    import numpy as np
    return lambda _mesh, tree, num_parts=None: _on(
        parts, jax.tree.map(np.asarray, tree))


def _compiled_text(jitted, *args, **static):
    return jitted.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("kind", ["sum", "min"])
@pytest.mark.parametrize("block_c,E", [(8, 128), (64, 128), (8, 512)])
def test_chunk_partials_kernel_compiles(v5e, block_c, E, kind, dtype):
    """The (block_c, E) shapes the engines use: pair-residual tiles
    (E=128, bc=64 via _block_partials / fixed in ops/pairs.py) and the
    default E=512 chunks at bc=8."""
    from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
    C = 2 * block_c
    text = _compiled_text(
        chunk_partials_pallas,
        jax.ShapeDtypeStruct((C, E), dtype, sharding=v5e),
        jax.ShapeDtypeStruct((C, E), jnp.int8, sharding=v5e),
        W=128, kind=kind, block_c=block_c)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype,kind", [
    ((37000, 16, 128), jnp.int32, "min"),     # kron20-serve, B = 16
    ((37003, 16, 128), jnp.float32, "min"),   # C no multiple of a block
    ((139000, 128), jnp.float32, "sum"),      # pr.kron21's residual
    ((1400000, 128), jnp.int32, "min"),       # past SCAN_BLOCKED_ABOVE
    ((20000, 20, 128), jnp.float32, "sum"),   # K = 20: 24 padded sublanes
    ((512, 128, 128), jnp.float32, "max"),    # a tall row: block of 128
])
def test_chunk_combine_kernel_compiles(v5e, shape, dtype, kind):
    """The one-pass chunk combine (ops/pallas_combine.py) at the
    cells' shapes with the block the payload's shape picks: the row
    kernel and the 8-chunk tile kernel lower through Mosaic, their
    blocks fit scoped VMEM, the SMEM flag block's layout is the one
    XLA gives it, and the partials are rewritten in place."""
    from lux_tpu.ops.pallas_combine import segmented_combine_pallas
    compiled = segmented_combine_pallas.lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=v5e),
        jax.ShapeDtypeStruct(shape[:1], jnp.bool_, sharding=v5e),
        kind=kind).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing" in text


def test_block_64x512_is_refused_and_block_partials_avoids_it(v5e):
    """(64, 512) overflows scoped VMEM — the case _block_partials'
    sizing rule guards: handed a 64-chunk E=512 block it must pick
    block_c=8 (and so compile)."""
    from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
    from lux_tpu.ops.tiled import _block_partials
    vals = jax.ShapeDtypeStruct((64, 512), jnp.float32, sharding=v5e)
    rel = jax.ShapeDtypeStruct((64, 512), jnp.int8, sharding=v5e)
    with pytest.raises(Exception, match="(?i)vmem|scoped|exhausted"):
        _compiled_text(chunk_partials_pallas, vals, rel, W=128,
                       kind="sum", block_c=64)

    def block(flat_state, src_b, rel_b):
        return _block_partials(flat_state, src_b, rel_b, None,
                               lambda v, w: v, "sum", 512, 128,
                               "pallas", False)

    text = _compiled_text(
        jax.jit(block),
        jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((64, 512), jnp.int32, sharding=v5e), rel)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_lane_shuffle_kernel_compiles(v5e, dtype):
    from lux_tpu.ops.pagegather import _lane_shuffle_pallas
    text = _compiled_text(
        jax.jit(_lane_shuffle_pallas),
        jax.ShapeDtypeStruct((64, 128), dtype, sharding=v5e),
        jax.ShapeDtypeStruct((64, 128), jnp.int32, sharding=v5e))
    assert "tpu_custom_call" in text


def test_kernels_compile_inside_shard_map(topo):
    """The Pallas kernels under a VMA-checked ``shard_map`` over the
    four-device parts mesh — the owner exchange's position on a real
    mesh.  ``pallas_call`` refuses an ``out_shape`` without ``vma``
    there; the CPU mesh tests never reach it (off-TPU ``auto``
    resolves to XLA), so the first four-chip run found it (PR 21)."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lux_tpu.ops.pagegather import _lane_shuffle_pallas
    from lux_tpu.ops.pallas_combine import segmented_combine_pallas
    from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
    from lux_tpu.parallel.mesh import PARTS_AXIS

    mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
    parts = NamedSharding(mesh, P(PARTS_AXIS))
    on_mesh = functools.partial(jax.shard_map, mesh=mesh,
                                in_specs=P(PARTS_AXIS),
                                out_specs=P(PARTS_AXIS))

    def sds(dtype):
        return jax.ShapeDtypeStruct((64, 128), dtype, sharding=parts)

    reduce_ = jax.jit(on_mesh(lambda v, r: chunk_partials_pallas(
        v, r, W=128, kind="min", block_c=8)))
    shuffle = jax.jit(on_mesh(_lane_shuffle_pallas))
    assert "tpu_custom_call" in _compiled_text(
        reduce_, sds(jnp.float32), sds(jnp.int8))
    assert "tpu_custom_call" in _compiled_text(
        shuffle, sds(jnp.float32), sds(jnp.int32))
    # the chunk combine: scalar tiles [C, 128] and rows [C, 16, 128]
    for trail in ((128,), (16, 128)):
        combine = jax.jit(on_mesh(
            lambda v, f: segmented_combine_pallas(v[0], f[0],
                                                  "min")[None]))
        assert "tpu_custom_call" in _compiled_text(
            combine,
            jax.ShapeDtypeStruct((4, 2048) + trail, jnp.int32,
                                 sharding=parts),
            jax.ShapeDtypeStruct((4, 2048), jnp.bool_, sharding=parts))


def _rmat12():
    from lux_tpu.convert import rmat_graph
    return rmat_graph(scale=12, edge_factor=8, seed=0)


def _assert_combine_kernel(layout, text):
    """Tiles span chunks, so the program holds the chunk combine: as
    the kernel under ``lux_combine``, with no tree scan's interleave
    (``lax.pad`` of the payload) beside it."""
    import re
    assert layout.needs_scan
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "lux_combine" in ln]
    assert calls and all("segmented_combine_pallas" in c for c in calls)
    pads = [ln for ln in text.splitlines()
            if re.search(r"= [fs]32\[[^]]*,128\]\S* pad\(", ln)
            and "lux_combine" in ln]
    assert not pads, pads[:2]


def _step_text(eng, v5e):
    jitted, args = eng.audit_variant("step")
    return _compiled_text(jitted, *_on(v5e, args()))


def test_pull_step_compiles_with_pallas(v5e):
    """One pull engine's per-iteration program (PageRank, RMAT12),
    reduce_method='pallas', compiled for v5e."""
    from lux_tpu.apps import pagerank
    from lux_tpu.engine.pull import PullEngine
    from lux_tpu.graph import ShardedGraph
    sg = ShardedGraph.build(_rmat12(), 1)
    eng = PullEngine(sg, pagerank.make_program(),
                     reduce_method="pallas")
    text = _step_text(eng, v5e)
    assert "tpu_custom_call" in text
    _assert_combine_kernel(eng.delivery.tiles, text)


@pytest.mark.parametrize("family", ["pull", "push", "push-symmetric"])
def test_mesh_owner_step_compiles_with_pallas(topo, monkeypatch,
                                              family):
    """The WHOLE per-iteration program of a four-part owner-exchange
    engine on the four-device mesh (shard_map + Pallas reduce + the
    routing collective), compiled for the v5e 2x2 topology — what
    `-np 4 -mesh 4 -exchange owner` runs on a four-chip host.  The
    topology's devices cannot hold data, so placement is replaced by
    abstract parts-sharded stand-ins; nothing executes."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lux_tpu.apps import pagerank, sssp
    from lux_tpu.engine import pull, push
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.parallel.mesh import PARTS_AXIS

    mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
    parts = NamedSharding(mesh, P(PARTS_AXIS))

    g = _rmat12()
    if family == "push-symmetric":      # builds the bottom-up step
        from lux_tpu.graph import Graph
        src, dst = g.edge_arrays()
        g = Graph.from_edges(np.concatenate([src, dst]),
                             np.concatenate([dst, src]), g.nv)
    sg = ShardedGraph.build(g, 4)
    if family == "pull":
        monkeypatch.setattr(pull, "shard_over_parts",
                            _abstract_shard(parts))
        eng = pull.PullEngine(sg, pagerank.make_program(), mesh=mesh,
                              exchange="owner",
                              reduce_method="pallas")
    else:
        monkeypatch.setattr(push, "shard_over_parts",
                            _abstract_shard(parts))
        eng = push.PushEngine(sg, sssp.make_program(0), mesh=mesh,
                              exchange="owner",
                              reduce_method="pallas")
        assert eng.pull == (family == "push-symmetric")
    jitted, args = eng.audit_variant("step")
    replicated = NamedSharding(mesh, P())
    args = [a if getattr(a, "sharding", None) is not None
            else _on(parts if a.ndim else replicated, a)
            for a in args()]
    text = _compiled_text(jitted, *args)
    assert "tpu_custom_call" in text
    _assert_combine_kernel(eng.delivery.owner, text)


@pytest.mark.parametrize("chips", [1, 4])
def test_pull_init_program_compiles_for_the_chip(topo, v5e, monkeypatch,
                                                 chips):
    """PR 36: the program that makes PageRank's first state on the
    devices (``PullEngine._init_program``), compiled for one v5e chip
    and for the four-device parts mesh: elementwise in arrays the
    devices hold, so no collective, no gather, and an output laid out
    as the parts sharding lays the state."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lux_tpu.apps import pagerank
    from lux_tpu.engine import pull
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.parallel.mesh import PARTS_AXIS

    mesh, parts = None, v5e
    if chips == 4:
        mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
        parts = NamedSharding(mesh, P(PARTS_AXIS))
        monkeypatch.setattr(pull, "shard_over_parts",
                            _abstract_shard(parts))
    sg = ShardedGraph.build(_rmat12(), chips)
    eng = pull.PullEngine(sg, pagerank.make_program(), mesh=mesh,
                          reduce_method="pallas")
    program, keys = eng._init_program
    assert keys == ["deg", "nvp"]
    compiled = program.lower(
        *_on(parts, [eng.arrays[k] for k in keys])).compile()
    text = compiled.as_text()
    assert not any(op in text for op in (
        "all-reduce", "all-gather", "all-to-all", "collective-permute",
        "gather(", "tpu_custom_call"))
    (out,) = jax.tree.leaves(compiled.output_shardings)
    assert out.is_equivalent_to(parts, 2)
    state = 4 * sg.vpad * sg.num_parts // chips         # bytes a device
    memory = compiled.memory_analysis()     # the chip pads rows to tiles
    assert state <= memory.output_size_in_bytes < 2 * state
    assert memory.temp_size_in_bytes <= memory.output_size_in_bytes


@pytest.mark.parametrize("symmetric", [False, True],
                         ids=["directed", "symmetric-bottom-up"])
def test_push_step_compiles_with_pallas(v5e, symmetric):
    """One push engine's per-iteration program (SSSP, RMAT12: dense
    iteration + sparse-frontier branch), reduce_method='pallas'; on
    the symmetrized graph with the bottom-up step (two-way slots on
    the labels with the queue behind them, the pulled buffer's
    write-back)."""
    import numpy as np

    from lux_tpu.apps import sssp
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import Graph, ShardedGraph
    g = _rmat12()
    if symmetric:
        src, dst = g.edge_arrays()
        g = Graph.from_edges(np.concatenate([src, dst]),
                             np.concatenate([dst, src]), g.nv)
    sg = ShardedGraph.build(g, 1)
    eng = PushEngine(sg, sssp.make_program(0),
                     reduce_method="pallas")
    assert eng.pull == symmetric
    text = _step_text(eng, v5e)
    assert "tpu_custom_call" in text
    _assert_combine_kernel(eng.delivery.tiles, text)


def test_components_ladder_without_the_bottom_up_step_compiles(v5e):
    """``cc.indochina``'s program at the cell's SHAPE (the web-crawl
    generator's directed graph at the configuration's rehearsal size,
    degree-relabelled, pair rows on, sparse view on; the cell's full
    size takes minutes of host preparation and is compiled by hand
    before a chip run): the whole ``converge`` loop of the unbatched
    max-label engine, ``reduce_method='pallas'``, compiled for v5e.  A
    directed graph builds no bottom-up step, so the ladder is the
    one-way one, and the carry's fill words come out as uint32
    [4, 2]."""
    import json
    import os

    from benchmarks.reference import webgraph
    from lux_tpu.apps import components
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "indochina-components.json")) as f:
        c = json.load(f)
    nv, arcs = c["rehearsal"]["vertices"], c["rehearsal"]["arcs"]
    src, dst = webgraph.web_arcs(
        nv, arcs, c["graph_seed"],
        **{k: c[k] for k in webgraph.PARAMETERS})
    pair = c["engine"]["pair_threshold"]
    g, _perm, starts = pair_relabel(Graph.from_edges(src, dst, nv), 1,
                                    pair_threshold=pair)
    sg = ShardedGraph.build(g, 1, starts=starts, pair_threshold=pair)
    eng = PushEngine(sg, components.make_program(),
                     reduce_method="pallas", pair_threshold=pair,
                     pair_min_fill=c["engine"]["pair_min_fill"],
                     enable_sparse=c["engine"]["enable_sparse"])
    assert not eng.pull and len(eng.queue_rungs) == 2 \
        and len(eng.budget_rungs) == 2
    jitted, args = eng.audit_variant("converge")
    compiled = jitted.lower(*_on(v5e, args())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "lux_q0" in text and "lux_eb1" in text
    assert "lux_pull" not in text
    fill = jax.tree.leaves(jax.eval_shape(jitted, *args()))[-1]
    assert (fill.shape, fill.dtype) == ((4, 2), jnp.uint32)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_serving_column_programs_compile_at_cell_size(topo, v5e, chips):
    """The push serving boundary's two device programs (serve.py
    ``_take_column``, ``_start_columns``) at ``ksssp.kron20.closed``'s
    state, ``[P, 2**20 / P, 16]`` int32 + bool on one chip and sharded
    over the four-device parts mesh: the reset rewrites the donated
    state in place (no second copy of it on a device) and neither
    program needs a collective."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lux_tpu import serve
    from lux_tpu.parallel.mesh import PARTS_AXIS

    if chips == 1:
        parts = small = v5e
    else:
        mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
        parts = NamedSharding(mesh, P(PARTS_AXIS))
        small = NamedSharding(mesh, P())
    shape, B = (chips, (1 << 20) // chips, 16), 16

    def sds(shape, dtype, sharding=small):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = (4 + 1) * (1 << 20) * B // chips        # bytes a device
    active = sds(shape, jnp.bool_, parts)
    for dtype in (jnp.int32, jnp.float32):      # hops, weighted sssp
        label = sds(shape, dtype, parts)
        take = jax.jit(serve._take_column).lower(
            label, sds((), jnp.int32)).compile()
        reset = jax.jit(
            serve._start_columns, donate_argnums=(0, 1),
            out_shardings=(parts, parts)).lower(
                label, active, sds((chips,), jnp.int32),
                sds((B,), jnp.bool_), sds((B,), jnp.int32),
                sds((B,), dtype), sds((), dtype)).compile()
        assert take.memory_analysis().output_size_in_bytes \
            == 4 * (1 << 20) // chips
        mem = reset.memory_analysis()
        assert mem.alias_size_in_bytes == state
        assert mem.temp_size_in_bytes < state // 16
        for compiled in (take, reset):
            text = compiled.as_text()
            assert not any(op in text for op in (
                "all-reduce", "all-gather", "all-to-all",
                "collective-permute"))


@pytest.mark.parametrize("chips", [1, 4])
def test_pull_serving_programs_compile_at_cell_size(topo, v5e, chips):
    """The pull serving boundary's device programs (serve.py, PR 27)
    at ``mixed.kron20.closed``'s state, ``[P, 2**20 / P, 16]`` float32
    on one chip and sharded over the four-device parts mesh:
    ``_start_columns`` with the reset table in the frontier's place
    and ``_put_column`` rewrite their donated tables in place, and the
    residual's only collective across parts is one all-reduce of its
    ``[B]`` result; the live-graph correction (``_delta_degrees``,
    ``_delta_mass``; one gather, one scatter) compiles at a delta
    block of 4096 slots."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lux_tpu import serve
    from lux_tpu.parallel.mesh import PARTS_AXIS

    if chips == 1:
        parts = small = v5e
    else:
        mesh = Mesh(np.asarray(topo.devices), (PARTS_AXIS,))
        parts = NamedSharding(mesh, P(PARTS_AXIS))
        small = NamedSharding(mesh, P())
    shape, B, cap = (chips, (1 << 20) // chips, 16), 16, 4096

    def sds(shape, dtype, sharding=small):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    table = 4 * (1 << 20) * B // chips              # bytes a device
    state = sds(shape, jnp.float32, parts)
    rows, cols = sds((chips,), jnp.int32), sds((B,), jnp.bool_)
    reset = jax.jit(
        serve._start_columns, donate_argnums=(0, 1),
        out_shardings=(parts, parts)).lower(
            state, state, rows, cols, sds((B,), jnp.int32),
            sds((B,), jnp.float32), sds((), jnp.float32)).compile()
    assert reset.memory_analysis().alias_size_in_bytes == 2 * table
    put = jax.jit(serve._put_column, donate_argnums=0,
                  out_shardings=parts).lower(
        state, sds(shape[:2], jnp.float32), sds((), jnp.int32)).compile()
    assert put.memory_analysis().alias_size_in_bytes == table
    residual = jax.jit(serve._column_residuals).lower(
        state, state, rows).compile()
    assert residual.memory_analysis().output_size_in_bytes <= 512
    for compiled in (reset, put, residual):
        assert compiled.memory_analysis().temp_size_in_bytes < table // 8
        text = compiled.as_text()
        assert not any(op in text for op in (
            "all-gather", "all-to-all", "collective-permute"))
        assert ("all-reduce" in text) == (compiled is residual
                                          and chips > 1)
    delta = [sds((cap,), d) for d in (jnp.int32, jnp.int32, jnp.float32,
                                      jnp.int32, jnp.int32)]
    jax.jit(serve._delta_degrees, donate_argnums=0,
            out_shardings=parts).lower(
        state, cols, sds((B,), jnp.int32), *delta).compile()
    jax.jit(functools.partial(serve._delta_mass, alpha=0.15),
            donate_argnums=0, out_shardings=parts).lower(
        state, state, sds(shape[:2], jnp.int32, parts), state,
        sds((B,), jnp.int32), *delta).compile()


@pytest.mark.parametrize("dtype,exact", [(jnp.float32, True),
                                         (jnp.int32, False)])
def test_mxu_sum_of_floats_is_not_rounded_to_bfloat16(v5e, dtype, exact):
    """The MXU one-hot sum at the serving cell's payload (16 query
    lanes) and the matmul scan that joins its chunks: a float32
    payload asks the chip's compiler for HIGHEST (three exact
    bfloat16 parts against the 0/1 operand), where the default would
    round it to bfloat16 — what `mixed.kron20.closed` first read from
    personalized PageRank on the chip (PERF.md, PR 26); an integer
    payload is exact at the default."""
    from lux_tpu.ops.tiled import _segscan_matmul, chunk_partials
    C, E, W, B = 256, 512, 128, 16
    vals = jax.ShapeDtypeStruct((C, E, B), dtype, sharding=v5e)
    rel = jax.ShapeDtypeStruct((C, E), jnp.int32, sharding=v5e)
    parts = jax.ShapeDtypeStruct((C, W, B), dtype, sharding=v5e)
    start = jax.ShapeDtypeStruct((C,), jnp.bool_, sharding=v5e)
    for text in (
            _compiled_text(jax.jit(lambda v, r: chunk_partials(
                v, r, W, "sum", use_mxu=True)), vals, rel),
            _compiled_text(jax.jit(_segscan_matmul), parts, start)):
        assert ("operand_precision={highest,highest}" in text) == exact
