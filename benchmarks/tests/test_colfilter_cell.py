"""``cf.netflix``'s own pieces: the rating-matrix generator, the
float64 reference against a loop worked edge by edge, the bfloat16
control against the configuration's limits, the roofline's counts,
and rehearsals with the timed path broken underneath."""

import types

import numpy as np
import pytest

from benchmarks import (control_colfilter, dot_rooflines, harness,
                        ratings_cache)
from benchmarks.readers import dot_roofline
from benchmarks.reference import colfilter as ref
from benchmarks.reference import ratings as gen

CONFIG = harness.load_json(
    harness.HERE + "/configs/netflix-colfilter.json")
LIMITS = {k: v for k, v in CONFIG["guarantees"].items()
          if not k.startswith("_")}
SHAPE = dict(user_skew=CONFIG["user_skew"],
             item_skew=CONFIG["item_skew"],
             marginal=CONFIG["rating_marginal"])


@pytest.fixture(scope="module")
def small():
    """(users, items, user, item, rating) of a 1/200 matrix."""
    users, items, ratings = 2401, 89, 60000
    return (users, items) + gen.rating_pairs(users, items, ratings, 7,
                                             **SHAPE)


def test_the_configuration_states_the_sources_counts():
    c = CONFIG
    assert c["users"] + c["items"] == c["vertices"] == 497959
    assert 2 * c["ratings"] == c["stored_edges"] == 200961014
    assert c["program_constants"]["K"] == ref.K == 20
    assert (c["program_constants"]["LAMBDA"], ref.LAMBDA) == (0.001,) * 2
    assert c["program_constants"]["GAMMA"] == ref.GAMMA == 3.5e-7
    assert c["reduced"] == [] and c["num_parts"] == 1
    from lux_tpu.apps import colfilter as app    # the program's own
    assert (app.K, app.LAMBDA, app.GAMMA) == (ref.K, ref.LAMBDA,
                                              ref.GAMMA)


@pytest.mark.parametrize("users,items,ratings", [
    (2401, 89, 60000), (300, 20, 5500), (50, 7, 350)])
def test_generator_gives_exactly_the_asked_unique_pairs(users, items,
                                                        ratings):
    user, item, rating = gen.rating_pairs(users, items, ratings, 3,
                                          **SHAPE)
    key = user.astype(np.int64) * items + item
    assert len(key) == ratings == len(np.unique(key))
    assert np.all(np.diff(key) > 0)             # sorted by (user, item)
    assert user.max() < users and item.max() < items
    assert set(np.unique(rating)) <= {1, 2, 3, 4, 5}


def test_generator_is_seeded_and_skewed(small):
    users, items, user, item, rating = small
    again = gen.rating_pairs(users, items, len(user), 7, **SHAPE)
    assert all(np.array_equal(a, b)
               for a, b in zip((user, item, rating), again))
    other = gen.rating_pairs(users, items, len(user), 8, **SHAPE)
    assert not np.array_equal(other[1], item)
    per_item = np.bincount(item, minlength=items)
    per_user = np.bincount(user, minlength=users)
    assert per_item[:5].mean() > 3 * per_item[-20:].mean()
    assert per_user[:50].mean() > 2 * per_user[-500:].mean()
    share = np.bincount(rating, minlength=6)[1:] / len(rating)
    assert np.abs(share - CONFIG["rating_marginal"]).max() < 0.01


def test_more_ratings_than_cells_is_refused():
    with pytest.raises(ValueError):
        gen.rating_pairs(10, 3, 31, 1, **SHAPE)


def test_both_directions_bipartite_same_rating(small):
    users, items, user, item, rating = small
    src, dst, w = gen.both_directions(user, item, rating, users)
    n = len(user)
    assert len(src) == 2 * n and w.dtype == np.int32
    assert np.all(src[:n] < users) and np.all(dst[:n] >= users)
    assert np.array_equal(src[:n], dst[n:])
    assert np.array_equal(dst[:n], src[n:])
    assert np.array_equal(w[:n], w[n:])
    assert dst.max() == users + item.max()


def test_by_destination_is_the_same_edge_set_sorted(small):
    users, items, user, item, rating = small
    offsets, src, rat = gen.by_destination(user, item, rating, users,
                                           items)
    s, d, w = gen.both_directions(user, item, rating, users)
    assert offsets[0] == 0 and offsets[-1] == len(s) == len(src)
    dst = np.repeat(np.arange(users + items), np.diff(offsets))
    want = sorted(zip(d.tolist(), s.tolist(), w.tolist()))
    got = sorted(zip(dst.tolist(), src.tolist(), rat.tolist()))
    assert got == want


def brute_force(offsets, src, rating, iterations, state):
    """The published sweep, one edge at a time."""
    nv = len(offsets) - 1
    state = state.copy()
    for _ in range(iterations):
        new = state.copy()
        for d in range(nv):
            acc = np.zeros(state.shape[1])
            for e in range(offsets[d], offsets[d + 1]):
                s = src[e]
                acc += (rating[e] - state[s] @ state[d]) * state[s]
            new[d] = state[d] + ref.GAMMA * (acc - ref.LAMBDA * state[d])
        state = new
    return state


@pytest.mark.parametrize("start", ["uniform", "random"])
def test_reference_equals_the_loop_on_a_tiny_matrix(start):
    users, items = 23, 5
    user, item, rating = gen.rating_pairs(users, items, 60, 2, **SHAPE)
    offsets, src, rat = gen.by_destination(user, item, rating, users,
                                           items)
    state = (ref.initial_factors(users + items) if start == "uniform"
             else np.random.default_rng(4).random((users + items, ref.K)))
    want = brute_force(offsets, src, rat, 3, state)
    for block in (1 << 19, 16):             # one block; many blocks
        got = ref.sweeps(offsets, src, rat, 3, state=state,
                         block_edges=block, workers=3)
        np.testing.assert_allclose(got, want, rtol=1e-14)


def test_reference_handles_vertices_without_edges():
    # user 2 and item 1 have no rating
    user = np.array([0, 1, 3], np.uint32)
    item = np.array([0, 0, 2], np.uint32)
    rating = np.array([5, 1, 3], np.uint8)
    offsets, src, rat = gen.by_destination(user, item, rating, 4, 3)
    init = ref.initial_factors(7)
    got = ref.sweeps(offsets, src, rat, 2)
    np.testing.assert_allclose(got, brute_force(offsets, src, rat, 2,
                                                init), rtol=1e-14)
    decay = (1 - ref.GAMMA * ref.LAMBDA) ** 2
    np.testing.assert_allclose(got[[2, 5]], init[[2, 5]] * decay,
                               rtol=1e-15)


def test_rmse_is_over_all_stored_edges(small):
    users, items, user, item, rating = small
    offsets, src, rat = gen.by_destination(user, item, rating, users,
                                           items)
    state = np.random.default_rng(1).random((users + items, ref.K))
    dst = np.repeat(np.arange(users + items), np.diff(offsets))
    err = rat - np.einsum("ek,ek->e", state[src], state[dst])
    assert ref.rmse(offsets, src, rat, state) == pytest.approx(
        np.sqrt(np.mean(err * err)), rel=1e-12)


@pytest.fixture(scope="module")
def control(small):
    users, items, user, item, rating = small
    edges = gen.by_destination(user, item, rating, users, items)
    return edges, control_colfilter.control_numbers(
        *edges, CONFIG["iterations"])


def test_bfloat16_contractions_fail_the_comparison(control):
    """The control: bfloat16 operands in the dot have to fail a limit
    of the configuration; a float32 state has to pass them all."""
    (offsets, src, rat), low = control
    for name, nums in low.items():
        assert control_colfilter.failed_limits(nums, LIMITS), (name, nums)
    assert low["dot"]["factor_delta_l2_rel_err"] > \
        2 * LIMITS["factor_delta_l2_rel_err"]
    want = ref.sweeps(offsets, src, rat, CONFIG["iterations"])
    init = ref.initial_factors(len(offsets) - 1)
    f32 = init.astype(np.float32)               # float32 STATE, rounded
    for _ in range(CONFIG["iterations"]):       # every sweep
        f32 = ref.sweeps(offsets, src, rat, 1,
                         state=f32).astype(np.float32)
    rm = [ref.rmse(offsets, src, rat, x) for x in (f32, want, init)]
    sound = ref.compare_factors(f32, want, init, *rm)
    assert not control_colfilter.failed_limits(sound, LIMITS), sound


def test_a_sweep_too_few_and_a_state_unchanged_fail(control):
    (offsets, src, rat), _low = control
    n = CONFIG["iterations"]
    want = ref.sweeps(offsets, src, rat, n)
    init = ref.initial_factors(len(offsets) - 1)
    rm_want, rm_init = (ref.rmse(offsets, src, rat, x)
                        for x in (want, init))
    short = ref.sweeps(offsets, src, rat, n - 1)
    nums = ref.compare_factors(short, want, init,
                               ref.rmse(offsets, src, rat, short),
                               rm_want, rm_init)
    assert nums["factor_delta_l2_rel_err"] > 0.1
    nums = ref.compare_factors(init, want, init, rm_init, rm_want,
                               rm_init)
    assert nums["factor_delta_l2_rel_err"] == pytest.approx(1.0)
    assert nums["rmse_not_falling"] == 1


def test_roofline_counts_are_the_mathematics():
    c = CONFIG
    nv, e, k = c["vertices"], c["stored_edges"], 20
    assert dot_rooflines.least_bytes_per_iteration(nv, e, k) == \
        8 * e + 2 * 4 * k * nv
    assert dot_rooflines.least_flops_per_iteration(e, k) == 4 * k * e
    run = types.SimpleNamespace(
        config=c, chips=1, graph={"nv": nv, "stored_edges": e},
        peaks=harness.device_peaks("TPU v5 lite"))
    least, bound = dot_roofline.least_seconds(run)
    # 1.69 GB over 819 GB/s against 16 GFLOP over 197 TFLOP/s
    assert bound == "hbm"
    assert least == pytest.approx((8 * e + 160 * nv) / 819e9)
    assert 1.9e-3 < least < 2.2e-3


def test_roofline_reader_is_silent_without_its_scope():
    """On a program without the scope (the parent), and on the CPU."""
    run = types.SimpleNamespace(
        config=CONFIG, chips=1, peaks=None, trace_summary=None,
        counters={}, graph={"nv": 10, "stored_edges": 20})
    spec = {"scopes": ["lux_dot_reduce"]}
    assert dot_roofline.read(spec, run) is None
    run.peaks = harness.device_peaks("TPU v5 lite")
    assert dot_roofline.read(spec, run) is None
    run.trace_summary = types.SimpleNamespace(
        scope_seconds=lambda *scopes: 0.0)
    run.counters = {"traced_iters": 5}
    assert dot_roofline.read(spec, run) is None
    run.trace_summary = types.SimpleNamespace(
        scope_seconds=lambda *scopes: 5 * 1e-6)
    assert dot_roofline.read(spec, run) == pytest.approx(
        100 * dot_roofline.least_seconds(run)[0] / 1e-6)


def _run(**kw):
    return harness.run_cell("cf.netflix", 2**31 + 9, 0.5, False,
                            rehearsal=True, **kw)


def test_a_solve_a_sweep_short_is_not_correct(monkeypatch):
    from lux_tpu.engine.pull import PullEngine
    real = PullEngine.run
    monkeypatch.setattr(
        PullEngine, "run",
        lambda self, state, n, **kw: real(self, state, n - 1, **kw))
    r = _run()
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0


def test_a_state_returned_unchanged_is_not_correct(monkeypatch):
    from lux_tpu.engine.pull import PullEngine
    monkeypatch.setattr(PullEngine, "run",
                        lambda self, state, n, **kw: state)
    r = _run()
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0


def test_cache_entry_holds_the_weighted_file_and_the_references_edges(
        tmp_path, monkeypatch):
    from benchmarks import graphs
    from lux_tpu.graph import Graph
    monkeypatch.setattr(graphs, "GRAPHS", str(tmp_path))
    paths = ratings_cache.ensure(301, 31, 4000, 5, CONFIG["user_skew"],
                                 CONFIG["item_skew"],
                                 CONFIG["rating_marginal"])
    assert paths["generated_edges"] == 8000
    g = Graph.from_file(paths["lux"], weighted=None)
    assert (g.nv, g.ne) == (332, 8000) and g.weights is not None
    offsets, src, rat = ratings_cache.load_reference(paths)
    assert np.array_equal(np.asarray(g.row_ptrs, np.int64), offsets[1:])
    # the program's file and the reference's arrays: the same edges
    # (the converter orders a destination's edges by source, as the
    # reference's are)
    assert np.array_equal(np.asarray(g.col_idx), src)
    assert np.array_equal(np.asarray(g.weights), rat)
    again = ratings_cache.ensure(301, 31, 4000, 5, 0.0, 0.0, [1])
    assert again == paths                       # found, not made anew
