"""Collaborative filtering at the NetFlix SHAPE, small: a seeded
bipartite rating matrix with hubs on the item side (some thousands of
users, about a hundred items, ratings 1..5, both directions, an odd
vertex count) through ``apps.colfilter.build_engine`` as the
benchmark's runner builds it (``pair_relabel``, ``pair_threshold`` 16,
``pair_min_fill`` "auto", np = 1), against the plain reference
``reference_colfilter`` ON THE LEARNED DISPLACEMENT: GAMMA = 3.5e-7
moves a factor by 1e-4 of its size, so ``allclose`` on the state
alone would pass a wrong sweep."""

import re

import jax
import numpy as np
import pytest

from lux_tpu import prepstore, telemetry
from lux_tpu.apps import colfilter
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
from lux_tpu.ops import pairs as pair_ops

USERS, ITEMS, RATINGS = 3000, 101, 60000
ITERS = 4
PAIR = dict(pair_threshold=16, pair_min_fill="auto")


def rating_graph(users=USERS, items=ITEMS, ratings=RATINGS, seed=3):
    """Unique (user, item) pairs, endpoint popularity ~ rank^-skew,
    each stored in both directions with the same rating."""
    rng = np.random.default_rng(seed)

    def ranks(n, skew, count):
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -skew)
        return np.minimum(np.searchsorted(cdf / cdf[-1],
                                          rng.random(count)), n - 1)
    key = np.unique(ranks(users, 0.6, ratings) * items
                    + ranks(items, 0.9, ratings))
    u = (key // items).astype(np.uint32)
    i = (key % items + users).astype(np.uint32)
    w = rng.choice(np.arange(1, 6, dtype=np.int32), size=len(key),
                   p=[0.05, 0.10, 0.23, 0.34, 0.28])
    return Graph.from_edges(np.concatenate([u, i]),
                            np.concatenate([i, u]), users + items,
                            weights=np.concatenate([w, w]))


@pytest.fixture(scope="module")
def g():
    return rating_graph()


# layout -> (pair rows, pair_stream)
LAYOUTS = {"pairs-streamed": (True, True),
           "pairs-monolithic": (True, False), "no-pairs": (False, None)}


def runner_engine(g, pair=True, pair_stream=None):
    """-> (engine, perm or None), built as benchmarks/runners/
    batch_colfilter.py builds it."""
    opts = dict(PAIR) if pair else {}
    perm = starts = None
    g_run = g
    if pair:
        g_run, perm, starts = pair_relabel(g, 1, pair_threshold=16)
    sg = ShardedGraph.build(g_run, 1, starts=starts,
                            pair_threshold=opts.get("pair_threshold"))
    eng = colfilter.build_engine(g_run, 1, None, sg=sg,
                                 pair_stream=pair_stream, **opts)
    return eng, perm


def solve(eng, perm, init=None):
    """ITERS sweeps -> factors [nv, K] in the generator's vertex ids;
    ``init`` [nv, K] (generator ids) replaces the uniform start."""
    if init is None:
        state = eng.init_state()
    else:
        first = np.asarray(eng.program.init(eng.sg))
        order = np.arange(len(init)) if perm is None else perm
        first[0, :len(init)] = init[order]
        state = eng.place(first)
    got = eng.unpad(eng.run(state, ITERS))
    if perm is None:
        return got
    out = np.empty_like(got)
    out[perm] = got
    return out


def learned_gap(got, want, init):
    return (np.linalg.norm((got - init) - (want - init))
            / np.linalg.norm(want - init))


def inits(nv):
    """Starting factors, float32 values held in float64 so that engine
    and reference start from the same numbers: the program's uniform
    sqrt(1/K), and seeded random factors in [0, 1) (a uniform state
    hides a wrong lane select: every lane's dot is the same)."""
    uniform = np.full((nv, colfilter.K), np.sqrt(1.0 / colfilter.K),
                      dtype=np.float32)
    seeded = np.random.default_rng(11).random(
        (nv, colfilter.K), dtype=np.float32)
    return {"uniform": uniform.astype(np.float64),
            "random": seeded.astype(np.float64)}


# what float32 STATE costs on the learned displacement: a factor moves
# by about 1e-4 in ITERS sweeps and is rounded to 2**-24 of its size
# every sweep (1.5e-8 at 0.22, 6e-8 below 1), so 1e-4 .. 1e-3 of the
# displacement is rounding; a wrong lane select, a dropped class of
# pair rows or a missing sweep reads 1e-1 and up
LEARNED_GAP = {"uniform": 1e-3, "random": 3e-3}


def test_the_matrix_has_the_deployments_shape(g):
    src, dst = g.edge_arrays()
    assert g.nv == USERS + ITEMS and g.nv % 2 == 1 and g.nv % 128
    assert g.ne % 2 == 0 and set(np.unique(g.weights)) == {1, 2, 3, 4, 5}
    assert np.all((src < USERS) != (dst < USERS))         # bipartite
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert all((d, s) in fwd for s, d in list(fwd)[:2000])
    # hubs on the item side: every item outweighs the busiest user
    deg = g.in_degrees()
    assert deg[USERS:].mean() > 10 * deg[:USERS].mean()


@pytest.mark.parametrize("init", ["uniform", "random"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_learned_displacement_matches_the_reference(g, layout, init):
    eng, perm = runner_engine(g, *LAYOUTS[layout])
    assert (eng.pairs is not None) == (layout != "no-pairs")
    assert eng.pair_dot_stream == (layout == "pairs-streamed")
    start = inits(g.nv)[init]
    want = colfilter.reference_colfilter(g, ITERS, init=start)
    got = solve(eng, perm, None if init == "uniform" else
                start.astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    gap = learned_gap(got, want, start)
    print(f"learned gap {layout} {init}: {gap:.3g}")
    assert gap < LEARNED_GAP[init]
    assert colfilter.rmse(g, got) < colfilter.rmse(g, start)


def test_pair_rows_do_most_of_the_work_on_a_hub_graph(g):
    eng, _perm = runner_engine(g)
    assert eng.pairs.stats["coverage"] > 0.7


def test_segment_sum_reference_equals_the_scatter_add(g):
    """reference_colfilter's reduceat against np.add.at on a slice of
    the matrix small enough for the scatter."""
    small = rating_graph(301, 31, 4000, seed=5)
    src, dst = small.edge_arrays()
    w = np.asarray(small.weights, np.float64)
    state = np.full((small.nv, colfilter.K), np.sqrt(1 / colfilter.K))
    for _ in range(3):
        err = w - np.einsum("ek,ek->e", state[src], state[dst])
        acc = np.zeros_like(state)
        np.add.at(acc, dst, err[:, None] * state[src])
        state = state + colfilter.GAMMA * (acc - colfilter.LAMBDA * state)
    np.testing.assert_allclose(colfilter.reference_colfilter(small, 3),
                               state, rtol=1e-13)


def step_text(eng):
    jitted, args = eng.audit_variant("step")
    return jitted.lower(*args()).as_text(debug_info=True)


@pytest.mark.parametrize("pair", [True, False])
def test_scopes_split_the_dot_path(g, pair):
    text = step_text(runner_engine(g, pair=pair)[0])
    # op paths read ".../vmap(lux_dot_reduce)/lux_dot_pairs/..."
    inside = set(re.findall(r"lux_dot_reduce\)?/(lux_dot_[a-z]+)", text))
    assert inside == ({"lux_dot_residual", "lux_dot_pairs"} if pair
                      else {"lux_dot_residual"})


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_dot_path_contractions_keep_float32(g, layout):
    """Every contraction of the lowered step asks for HIGHEST: at the
    default precision a TPU rounds float32 operands to bfloat16."""
    eng, _ = runner_engine(g, *LAYOUTS[layout])
    dots = re.findall(r"stablehlo\.dot_general.*", step_text(eng))
    # residual: 2 einsums; pair rows: 2 a class (streamed) or 2
    assert len(dots) >= (4 if eng.pairs is not None else 2)
    assert all("HIGHEST" in d and "DEFAULT" not in d for d in dots), dots


def test_paged_dot_contractions_keep_float32(g):
    eng = colfilter.build_engine(g, 1, gather="paged")
    if eng.delivery.page_plan is None:
        pytest.skip("no page plan at this size")
    dots = re.findall(r"stablehlo\.dot_general.*", step_text(eng))
    assert dots and all("HIGHEST" in d for d in dots), dots


def test_dot_precision_is_for_floats_only():
    assert pair_ops.dot_precision(np.float32) == jax.lax.Precision.HIGHEST
    assert pair_ops.dot_precision(np.int32) is None


def plan_records(tip):
    return [r for r in telemetry.spans()
            if r["id"] > tip and r["name"] == "build.pair_plan"]


def test_pair_plan_counts_its_lanes_on_a_miss_and_on_a_hit(
        g, tmp_path, monkeypatch):
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(tmp_path / "store"))
    monkeypatch.setattr(prepstore, "MIN_EDGES", 0)
    telemetry.mark("test.tip")
    tip = telemetry.spans()[-1]["id"]
    counts = []
    for _ in range(2):                      # the miss, then the hit
        eng, _perm = runner_engine(rating_graph())
        counts.append(plan_records(tip)[-1]["counts"])
    stores = [r["counts"] for r in telemetry.spans()
              if r["id"] > tip and r["name"] == "prep.store"]
    assert any(c["miss"] for c in stores) and any(c["hit"] for c in stores)
    for c in counts:
        assert c["pair_lanes"] == pair_ops.W * eng.pairs.R
        assert c["pair_edges"] == eng.pairs.stats["covered"]
        assert 0 < c["pair_edges"] <= c["pair_lanes"]
    assert counts[0] == counts[1]


def test_no_pair_rows_counts_no_lanes():
    sparse = rating_graph(2001, 301, 3000, seed=9)
    telemetry.mark("test.tip")
    tip = telemetry.spans()[-1]["id"]
    eng, _perm = runner_engine(sparse)
    rec = plan_records(tip)[-1]["counts"]
    if eng.pairs is None:
        assert rec["pair_lanes"] == 0 and rec["pair_edges"] == 0
    else:
        assert rec["pair_lanes"] == pair_ops.W * eng.pairs.R


@pytest.mark.parametrize("block_rows", [1, 50, 150])
def test_a_slot_deeper_than_a_block_streams_in_chunks(block_rows):
    """A hub tile pair is ONE slot of hundreds of thousands of rows at
    the deployment's size (27 GB as one block): the streamed SDDMM
    must cut it into blocks, whole chunks and a remainder, and give
    the monolithic path's sums exactly (integer-valued state: every
    dot, message and sum is exact in float32)."""
    import jax.numpy as jnp
    g = rating_graph(1501, 41, 30000, seed=4)
    sg = ShardedGraph.build(g, 1, vpad_align=128)
    sp, _res = pair_ops.plan_sharded_pairs(sg, threshold=4)
    depths = [L for _c, L in sp.classes]
    assert max(depths) > 2 * block_rows + 1         # deep classes exist
    assert min(depths) <= block_rows or block_rows == 1
    k = colfilter.K
    state = np.random.default_rng(2).integers(
        0, 4, (sg.vpad, k)).astype(np.float32)

    def msg(s, dot, wt):
        return (wt - dot)[..., None] * s
    args = (sp, jnp.asarray(state), jnp.asarray(sp.rowbind[0]),
            jnp.asarray(sp.rel_dst[0]), jnp.asarray(sp.weight[0]),
            jnp.asarray(sp.row_tile[0]), jnp.asarray(sp.tile_pos[0]), 0,
            msg)
    row_bytes = 4 * pair_ops.W * (pair_ops.W + 4 * k)
    mono = np.asarray(pair_ops.pair_partial_dot(*args))
    strm = np.asarray(pair_ops.pair_partial_dot_streamed(
        *args, block_bytes=block_rows * row_bytes))
    np.testing.assert_array_equal(strm, mono)
