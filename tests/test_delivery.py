"""The delivery layer (lux_tpu/engine/delivery.py): both engines hold
the SAME layout for the same options, every layout's reduction equals
the plain segment reduce over the edge list, each rejected combination
has one error text whichever engine is asked, and nothing else under
``lux_tpu/engine/`` knows the layouts."""

import ast
import functools
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu.apps import pagerank, sssp
from lux_tpu.engine.pull import PullEngine
from lux_tpu.engine.push import PushEngine
from lux_tpu.graph import ShardedGraph, pair_relabel
from lux_tpu.ops.segment import segment_reduce

ENGINE_DIR = pathlib.Path(__file__).resolve().parent.parent \
    / "lux_tpu" / "engine"
NUM_PARTS = 2

LAYOUTS = {
    "tiled": {},
    "tiled+pairs": dict(pair_threshold=4),
    "paged": dict(gather="paged"),
    "pagemajor": dict(gather="pagemajor"),
    "owner": dict(exchange="owner"),
    "owner+pairs": dict(exchange="owner", pair_threshold=4),
}


@pytest.fixture(scope="module")
def rmat():
    from lux_tpu.convert import rmat_graph
    return rmat_graph(scale=10, edge_factor=8, seed=0)


@pytest.fixture(scope="module")
def engines(rmat):
    """layout name -> (full ShardedGraph, PullEngine [sum], PushEngine
    [min]), both engines built on that one sharding with the same
    delivery options."""
    @functools.cache
    def build(name):
        opts = LAYOUTS[name]
        pair = opts.get("pair_threshold")
        g, starts = rmat, None
        if pair is not None:
            g, _perm, starts = pair_relabel(rmat, NUM_PARTS,
                                            pair_threshold=pair)
        sg = ShardedGraph.build(g, NUM_PARTS, starts=starts,
                                pair_threshold=pair, vpad_align=128)
        return (sg, PullEngine(sg, pagerank.make_program(), **opts),
                PushEngine(sg, sssp.make_program(0), **opts))

    return build


def _delivery_arrays(eng):
    return {k: eng.arrays[k] for k in eng.delivery.keys}


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_both_engines_hold_the_same_layout(engines, name):
    _sg, pull, push = engines(name)
    a, b = _delivery_arrays(pull), _delivery_arrays(push)
    assert list(a) == list(b)
    for k in a:
        assert (a[k].shape, a[k].dtype) == (b[k].shape, b[k].dtype), k
        np.testing.assert_array_equal(np.asarray(a[k]),
                                      np.asarray(b[k]), err_msg=k)
    if "pairs" in name:
        assert pull.pairs is not None
        assert pull.pairs.stats["covered"] > 0
        assert pull.sg.ne_part.sum() < _sg.ne_part.sum()   # residual
    for attr in ("exchange", "gather", "use_mxu", "reduce_method",
                 "pair_stream", "stream_chunks"):
        assert getattr(pull, attr) == getattr(push, attr), attr


def _deliver(eng, rows, msg):
    """One dense reduction of the hand-made table ``rows [P, vpad]``
    through the engine's delivery, in whichever form it takes."""
    d, g = eng.delivery, _delivery_arrays(eng)
    if d.exchange == "owner":
        return d.owner_pairs(d.owner_generate(rows, msg, g), rows,
                             msg, g)
    flat = rows.reshape(-1)
    if d.fused:
        return jax.vmap(lambda gp: d.reduce_fused(flat, msg, gp))(g)
    return jax.vmap(lambda gp: d.reduce(
        flat, d.messages(flat, msg, gp), msg, gp))(g)


@pytest.mark.parametrize("kind", ["sum", "min"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_reduction_equals_plain_segment_reduce(engines, name, kind):
    sg, pull, push = engines(name)
    eng = pull if kind == "sum" else push
    assert eng.delivery.kind == kind
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.random((sg.num_parts, sg.vpad),
                                  dtype=np.float32))

    def msg(vals, w):
        return vals * 2 + 1

    got = np.asarray(jax.jit(lambda r: _deliver(eng, r, msg))(rows))
    flat = rows.reshape(-1)
    for p in range(sg.num_parts):
        want = segment_reduce(
            msg(jnp.take(flat, jnp.asarray(sg.src_slot[p])), None),
            jnp.asarray(sg.dst_local[p]), sg.vpad + 1, kind)[:sg.vpad]
        if kind == "min":       # no rounding: exact, +inf where empty
            np.testing.assert_array_equal(got[p], np.asarray(want))
        else:
            np.testing.assert_allclose(got[p], np.asarray(want),
                                       rtol=1e-5, atol=1e-6)


# -- (a2) the lane-aligned placement follows the program's batch --------

def _tiled_arrays(sg, aligned):
    from lux_tpu.ops.tiled import TiledLayout
    lay = TiledLayout.build(sg.row_ptr_local, sg.dst_local, sg.vpad,
                            aligned=aligned)
    want = dict(src_slot=lay.chunk(sg.src_slot), rel_dst=lay.rel_dst,
                chunk_start=lay.chunk_start, last_chunk=lay.last_chunk)
    if aligned:
        want["tile_rank"] = lay.tile_rank
    return lay, want


def _last_dense_layout():
    from lux_tpu import telemetry
    return [r for r in telemetry.spans()
            if r["name"] == "build.dense_layout"][-1]["counts"]


@pytest.mark.parametrize("family", ["pull", "push"])
def test_unbatched_delivery_builds_the_default_layout(rmat, family):
    """A program without a query batch gets TiledLayout.build's
    default arrays, element for element, no ``tile_rank``, and marks
    no aligned edge."""
    sg = ShardedGraph.build(rmat, NUM_PARTS, vpad_align=128)
    eng = _build(family, sg)
    counts = _last_dense_layout()
    lay, want = _tiled_arrays(sg, aligned=False)
    assert not eng.delivery.aligned
    assert eng.delivery.tiles.n_aligned == 0
    assert "tile_rank" not in eng.delivery.keys
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(eng.arrays[k]), v,
                                      err_msg=k)
    assert counts == dict(lay.counts(), aligned_edges=0, aligned_slots=0)
    assert counts["tiled_edges"] == sg.ne


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["two-step", "streamed"])
@pytest.mark.parametrize("family", ["pull", "push"])
def test_batched_delivery_builds_the_aligned_layout(rmat, family,
                                                    streamed):
    """A query-batched program (sum through the pull engine, min
    through the push engine) gets the aligned arrays, and its dense
    reduction of a [vpad, B] table equals the plain segment reduce of
    the same messages, in vertex order."""
    sg = ShardedGraph.build(rmat, NUM_PARTS, vpad_align=128)
    eng = _build(family, sg, batched=True, stream_msgs=streamed)
    counts = _last_dense_layout()
    lay, want = _tiled_arrays(sg, aligned=True)
    d = eng.delivery
    assert d.aligned and d.fused == streamed
    assert 0 < d.tiles.n_aligned <= d.tiles.n_chunks
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(eng.arrays[k]), v,
                                      err_msg=k)
    assert counts == lay.counts() and counts["aligned_edges"] > 0
    kind = d.kind
    rng = np.random.default_rng(9)
    rows = jnp.asarray(rng.integers(0, 256, (sg.num_parts, sg.vpad, 2))
                       .astype(np.float32 if kind == "sum" else np.int32))

    def msg(vals, w):
        return vals * 2 + 1

    g = _delivery_arrays(eng)
    flat = rows.reshape(-1, 2)
    if d.fused:
        got = jax.vmap(lambda gp: d.reduce_fused(flat, msg, gp))(g)
    else:
        got = jax.vmap(lambda gp: d.reduce(
            flat, d.messages(flat, msg, gp), msg, gp))(g)
    for p in range(sg.num_parts):
        want_p = segment_reduce(
            msg(jnp.take(flat, jnp.asarray(sg.src_slot[p]), axis=0),
                None),
            jnp.asarray(sg.dst_local[p]), sg.vpad + 1, kind)[:sg.vpad]
        # small integers: the float32 sums are exact as well
        np.testing.assert_array_equal(np.asarray(got[p]),
                                      np.asarray(want_p))


def test_batched_delivery_with_short_chunks_keeps_the_default(rmat):
    """Chunks that hold no whole depth row (tile_e under 128) cannot be
    aligned: the batched engine runs on the default layout."""
    sg = ShardedGraph.build(rmat, NUM_PARTS, vpad_align=128)
    with pytest.warns(UserWarning, match="not a multiple of 128"):
        eng = _build("push", sg, batched=True, tile_e=64)
    assert not eng.delivery.aligned
    assert eng.delivery.tiles.n_aligned == 0


# -- (b) rejected combinations ----------------------------------------

def _build(family, sg, batched=False, **opts):
    if family == "pull":
        prog = (pagerank.make_batched_program(
            pagerank.one_hot_resets(sg.nv, [0, 1])) if batched
            else pagerank.make_program())
        return PullEngine(sg, prog, **opts)
    prog = (sssp.make_batched_program([0, 1], False) if batched
            else sssp.make_program(0))
    return PushEngine(sg, prog, **opts)


REJECTED = {
    "paged subsumes pairs": (
        dict(gather="paged", pair_threshold=4), "subsumes pair delivery"),
    "pairs need the tiled layout": (
        dict(layout="flat", pair_threshold=4),
        "pair_threshold requires the tiled layout"),
    "no pairs on a query batch": (
        dict(batched=True, pair_threshold=4),
        "pair_threshold does not support query-batched programs"),
    "unknown exchange": (dict(exchange="bogus"), "unknown exchange"),
    "unknown layout": (dict(layout="bogus"), "unknown layout"),
    "unknown reduce_method": (
        dict(reduce_method="bogus"), "unknown reduce_method"),
    "unknown use_mxu": (dict(use_mxu="bogus"), "unknown use_mxu"),
    "unknown gather": (dict(gather="bogus"), "unknown gather"),
}


@pytest.mark.parametrize("case", list(REJECTED))
def test_rejected_combination_has_one_text(rmat, case):
    opts, phrase = REJECTED[case]
    sg = ShardedGraph.build(rmat, NUM_PARTS, vpad_align=128)
    texts = []
    for family in ("pull", "push"):
        with pytest.raises(ValueError, match=phrase) as err:
            _build(family, sg, **opts)
        texts.append(str(err.value))
    assert texts[0] == texts[1]
    # one copy of the text under lux_tpu/engine/ (the ops own theirs);
    # adjacent string literals joined, as the compiler joins them
    holders = [p.name for p in sorted(ENGINE_DIR.glob("*.py"))
               if phrase in re.sub(r'"\s*\n\s*f?"', "", p.read_text())]
    assert holders in ([], ["delivery.py"]), holders


def test_owner_needs_source_only_edge_values(rmat):
    import dataclasses
    sg = ShardedGraph.build(rmat, NUM_PARTS)
    bad = dataclasses.replace(pagerank.make_program(), needs_dst=True)
    with pytest.raises(ValueError, match="exchange='owner' supports"):
        PullEngine(sg, bad, exchange="owner")


def test_constructor_options_tile_w_and_stats_cap_are_constants(rmat):
    sg = ShardedGraph.build(rmat, NUM_PARTS)
    for family in ("pull", "push"):
        for gone in ("tile_w", "stats_cap"):
            with pytest.raises(TypeError, match=gone):
                _build(family, sg, **{gone: 128})
        eng = _build(family, sg)
        assert eng.tiles.W == 128
        from lux_tpu.telemetry import DEFAULT_STATS_CAP
        assert eng.stats_cap == DEFAULT_STATS_CAP


# -- (c) who may know what --------------------------------------------

def _imports(path):
    """(module, name) of every import in the file, at any depth."""
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            out += [(node.module or "", a.name) for a in node.names]
        elif isinstance(node, ast.Import):
            out += [(a.name, "") for a in node.names]
    return out


def test_delivery_imports_no_engine_app_or_serve():
    allowed = ("lux_tpu.ops", "lux_tpu.parallel", "lux_tpu.graph",
               "lux_tpu.scalemodel", "lux_tpu.telemetry")
    for mod, name in _imports(ENGINE_DIR / "delivery.py"):
        full = f"{mod}.{name}" if mod == "lux_tpu" else mod
        if full.startswith("lux_tpu"):
            assert full.startswith(allowed), (mod, name)


def test_push_imports_nothing_private_from_pull():
    for path in (ENGINE_DIR / "push.py", ENGINE_DIR / "delivery.py"):
        for mod, name in _imports(path):
            assert not (mod.startswith("lux_tpu.engine")
                        and name.startswith("_")), (path.name, mod, name)
    assert not any(mod == "lux_tpu.engine.pull"
                   for mod, _ in _imports(ENGINE_DIR / "push.py"))


LAYOUT_CALLS = ("pair_partial", "paged_partial", "owner_contribs",
                "OwnerLayout", "plan_sharded_pairs", "engine_page_plan")


@pytest.mark.parametrize("call", LAYOUT_CALLS)
def test_layout_calls_live_in_delivery_alone(call):
    def names(path):
        tree = ast.parse(path.read_text())
        return {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} \
            | {n.attr for n in ast.walk(tree)
               if isinstance(n, ast.Attribute)} \
            | {name for _, name in _imports(path)}

    holders = [p.name for p in sorted(ENGINE_DIR.glob("*.py"))
               if call in names(p)]
    assert holders == ["delivery.py"]
