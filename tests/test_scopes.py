"""The named scopes the benchmark reads exist in the programs its
cells run.

Where an iteration's time goes has one account: the ``jax.named_scope``
names on the ops of the program that runs, summed from a device trace
by ``benchmarks/trace_reduce.py``.  Every ``scope_ms.*`` per-layer
metric, every roofline share and the ledger's ``breakdown`` read those
names, so a renamed scope zeroes a metric without any result changing.
Here each cell's engine FORM is built small from the cell's own
configuration file (app, ``engine`` options, parts, mesh, batch), its
step and its fused loop are lowered on the CPU backend, and the scope
names are read from the lowered text's debug locations: what the
metric files name must be there.  The files are read, not copied.
"""

import functools
import glob
import json
import os
import re

import numpy as np
import pytest

# a scope name as the trace reduction itself finds one
from benchmarks.trace_reduce import SCOPE_RE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# collectives as StableHLO spells them (``collective: true`` metrics)
COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_gather|all_reduce|reduce_scatter|all_to_all|"
    r"collective_permute)\b")


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCHMARK = _load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
SCOPE_FILES = {
    os.path.basename(p)[:-len(".json")]: _load(p)
    for p in sorted(glob.glob(
        os.path.join(ROOT, "benchmarks", "layer_metrics",
                     "scope_ms.*.json")))}
# (metric, cell) for every scope metric and every cell that reports it
METRIC_CELLS = [(m["name"], cell) for m in BENCHMARK["per_layer"]
                if m["name"] in SCOPE_FILES for cell in m["workloads"]]
NAMED_SCOPES = sorted({s for spec in SCOPE_FILES.values()
                       for s in spec.get("scopes", ())})
# ``lux_gather_reduce`` is the FUSED delivery's scope (paged rows, or
# chunks streamed once a part's messages pass 1 GB): no cell is that
# large or paged yet, so its form is ``pr.kron21``'s configuration
# with the ``engine`` options of the cell PERF.md section 7 names for
# that side of the choice (``pr.kron21.paged``)
VARIANT_FORMS = {"pr.kron21": ({"gather": "paged"},)}
# what the ledger's ``breakdown`` lists for the serving cells
SERVING_SCOPES = {"push": ("lux_relax", "lux_reduce", "lux_aligned"),
                  "pull": ("lux_gather",)}


@functools.lru_cache(maxsize=None)
def _config(cell: str) -> dict:
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[
        CELLS[cell]["config"]]
    return _load(os.path.join(ROOT, entry["file"]))


def _kinds(c: dict) -> list:
    """The query kinds of a serving configuration (none: a batch
    cell)."""
    return c.get("kinds", [c["kind"]] if "kind" in c else [])


# (cell, kind) of every serving cell
SERVING = [(cell, kind) for cell in CELLS
           for kind in _kinds(_config(cell))]


def _small_graph(app: str, symmetrized: bool, weighted: bool = False,
                 weight_type: str = "float32"):
    """A graph of the cell's KIND at a size that lowers in a second:
    R-MAT scale 10 x 16, symmetrized where the cell's is (the push
    engine builds its bottom-up step on symmetric graphs only),
    integer ratings 1..5 as weights for colfilter; where the
    configuration says ``weighted``, weights of its ``weight_type``:
    float32 uniform in [0, 1), or int32 lengths 1..9999 (the program
    then runs int32 distances, ``apps/sssp.py``)."""
    from lux_tpu.apps import components
    from lux_tpu.convert import rmat_graph
    from lux_tpu.graph import Graph

    g = rmat_graph(scale=10, edge_factor=16, seed=1)
    if symmetrized:
        g = Graph.from_edges(*components.symmetrize(*g.edge_arrays()),
                             g.nv)
    if app == "colfilter":
        g.weights = np.random.default_rng(1).integers(
            1, 6, size=g.ne).astype(np.int32)
    elif weighted and weight_type == "int32":
        g.weights = np.random.default_rng(1).integers(
            1, 10_000, size=g.ne).astype(np.int32)
    elif weighted:
        g.weights = np.random.default_rng(1).random(
            g.ne, dtype=np.float32)
    return g


def _batch_engine(c: dict, engine=None):
    """The engine of a batch cell, built as ``benchmarks/runners/
    common.load_and_layout`` and the runners' ``prepare`` build it
    (``engine``: options in place of the configuration's)."""
    import importlib

    from lux_tpu.graph import ShardedGraph, pair_relabel
    from lux_tpu.parallel.mesh import make_mesh

    app = importlib.import_module("lux_tpu.apps." + c["app"])
    opts = c.get("engine", {}) if engine is None else engine
    num_parts, pair = int(c["num_parts"]), opts.get("pair_threshold")
    weighted = bool(c.get("weighted"))
    g = _small_graph(c["app"], bool(c.get("symmetrized")), weighted,
                     c.get("weight_type", "float32"))
    starts = None
    if pair is not None:
        g, _perm, starts = pair_relabel(g, num_parts,
                                        pair_threshold=pair)
    sg = ShardedGraph.build(g, num_parts, starts=starts,
                            pair_threshold=pair)
    mesh = make_mesh(int(c["mesh"])) if int(c.get("mesh", 1)) > 1 \
        else None
    kw = dict(num_parts=num_parts, mesh=mesh, sg=sg, **opts)
    if c["app"] == "sssp":
        kw.update(start_vertex=0, weighted=weighted)
    return app.build_engine(g, **kw)


def _serving_engines(c: dict) -> dict:
    """kind -> engine of a serving cell: ``serve.Server``'s own runners
    at a small ``batch``."""
    from lux_tpu import serve

    server = serve.Server(_small_graph("sssp", False), batch=4,
                          num_parts=int(c["num_parts"]))
    return {k: server._runner(k).eng for k in _kinds(c)}


def _lowered(eng) -> str:
    """The step and the fused loop the cells drive, lowered with debug
    locations (the scope names are in them)."""
    loop = "converge" if hasattr(eng, "converge") else "run"
    programs = eng.audit_programs()
    text = []
    for name in ("step", loop):
        jitted, args = programs[name]
        text.append(jitted.lower(*args()).as_text(debug_info=True))
    return "\n".join(text)


@functools.lru_cache(maxsize=None)
def _engines(cell: str) -> dict:
    """engine name -> engine of the cell's form(s)."""
    c = _config(cell)
    if "app" in c:
        return {c["app"]: _batch_engine(c)}
    return _serving_engines(c)


@functools.lru_cache(maxsize=None)
def _form(cell: str) -> dict:
    """engine name -> lowered text of the cell's engine form(s)."""
    return {k: _lowered(e) for k, e in _engines(cell).items()}


def _loop_carry(eng) -> int:
    """Leaves of the carry of the fused loop's outermost
    ``while_loop``."""
    import jax

    jitted, args = eng.audit_programs()["converge"]

    def outermost(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                return len(eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = outermost(sub)
                if found:
                    return found
        return 0

    return outermost(jitted.trace(*args()).jaxpr.jaxpr)


def _text(cell: str) -> str:
    return "\n".join(_form(cell).values())


@functools.lru_cache(maxsize=None)
def _scopes(cell: str) -> frozenset:
    return frozenset(SCOPE_RE.findall(_text(cell)))


@functools.lru_cache(maxsize=None)
def _variant_scopes(cell: str) -> set:
    return {s for engine in VARIANT_FORMS.get(cell, ())
            for s in SCOPE_RE.findall(
                _lowered(_batch_engine(_config(cell), engine)))}


def test_every_cell_has_a_form():
    """The table above covers the benchmark: a new cell needs a form
    here (its configuration names an app or serving kinds)."""
    for cell in CELLS:
        c = _config(cell)
        assert "app" in c or _kinds(c), cell
    assert METRIC_CELLS and NAMED_SCOPES and SERVING


@pytest.mark.parametrize("cell", [
    cell for cell in CELLS if "app" in _config(cell)
    and _config(cell).get("engine", {}).get("delta") is None])
def test_cells_without_delta_carry_no_bucket_scope(cell):
    """``lux_bucket`` is the bucket (delta-stepping) loop's alone: the
    batch cells whose configuration sets no ``engine.delta`` keep the
    programs they had."""
    assert "lux_bucket" not in _scopes(cell), cell


# the bucket loop's choice: the front's out-edge total (uint32)
# GREATER than the top budget rung (``PushEngine._spills``)
SPILLS_RE = re.compile(
    r"stablehlo\.compare\s+GT\b[^\n]*tensor<ui32>")
# (cell, engine) of every push engine a cell runs
PUSH_FORMS = [(cell, name) for cell, name in
              [(cell, _config(cell)["app"]) for cell in CELLS
               if "app" in _config(cell)] + SERVING
              if name in ("sssp", "components")]


@pytest.mark.parametrize("cell,name", PUSH_FORMS)
def test_only_the_bucket_loop_compares_a_fronts_out_edges(cell, name):
    """The choice by out-edge total is the bucket (delta-stepping)
    loop's alone.  Every push engine whose configuration sets no
    ``engine.delta`` keeps the plain loop: its carry is the four loop
    words, the ``took`` counts and, on an engine with the ladder, the
    four fills' eight words, and its text holds neither the compare
    nor ``lux_bucket``; the bucket loop carries its bound and its
    six own words more (``advances``, ``front_edges``' two,
    ``front_vertices``' two, ``edge_dense``)."""
    eng, text = _engines(cell)[name], _form(cell)[name]
    plain = 4 + 1 + 8 * int(eng._sparse_mode()[0])
    if eng.delta is None:
        assert _loop_carry(eng) == plain
        assert not SPILLS_RE.search(text)
        assert "lux_bucket" not in text
    else:
        assert _loop_carry(eng) == plain + 1 + 6
        assert SPILLS_RE.search(text)
        assert "lux_bucket" in text


def test_the_road_cell_runs_int32_labels_under_kernel_3s_scopes():
    """``ssspw.road.delta``'s form is built from its configuration's
    ``weight_type``: the int32 program, whose guarded scopes are those
    of ``ssspw.kron21.delta`` (the same loop on another label type)."""
    road, kron = "ssspw.road.delta", "ssspw.kron21.delta"
    eng = _engines(road)["sssp"]
    assert np.asarray(eng.program.identity).dtype == np.int32
    assert isinstance(eng.delta, int) and eng.delta > 0
    assert np.asarray(_engines(kron)["sssp"].program.identity
                      ).dtype == np.float32
    assert _scopes(road) == _scopes(kron)
    # ... less the two that read the dense branch: no trip of a road
    # search is dense, so on the chip their readers find nothing there
    mine = {m for m, cell in METRIC_CELLS if cell == road}
    assert mine <= {m for m, cell in METRIC_CELLS if cell == kron}
    assert not mine & {"scope_ms.dense", "scope_ms.combine"}
    assert {"scope_ms.sparse", "scope_ms.bucket"} <= mine


@pytest.mark.parametrize("metric,cell", METRIC_CELLS)
def test_scope_metric_finds_its_scope_in_the_cell(metric, cell):
    """(a) a ``scope_ms`` metric a cell reports reads something there:
    one of the file's scopes (or, for ``collective: true``, a
    collective) is in the cell's lowered program."""
    spec = SCOPE_FILES[metric]
    if spec.get("collective"):
        assert COLLECTIVE_RE.search(_text(cell)), (metric, cell)
        return
    assert set(spec["scopes"]) & _scopes(cell), (
        f"{metric} reads {spec['scopes']} but {cell}'s program has "
        f"only {sorted(_scopes(cell))}")


@pytest.mark.parametrize("scope", NAMED_SCOPES)
def test_named_scope_is_emitted_by_some_cell(scope):
    """(b) no metric file names a scope the programs dropped: a cell
    that reports the metric emits it, or that cell's variant form."""
    metrics = [m for m, spec in SCOPE_FILES.items()
               if scope in spec.get("scopes", ())]
    cells = sorted({cell for m, cell in METRIC_CELLS if m in metrics})
    assert any(scope in _scopes(cell) for cell in cells) or any(
        scope in _variant_scopes(cell) for cell in cells), (
        f"{scope} (named by {metrics}) is in none of {cells}")


@pytest.mark.parametrize("cell,kind", SERVING)
def test_serving_forms_emit_the_breakdown_scopes(cell, kind):
    """(c) the query-batched push form (aligned placement) and the
    query-batched pull form carry the names the ledger's ``breakdown``
    lists for the serving cells."""
    found = set(SCOPE_RE.findall(_form(cell)[kind]))
    family = "pull" if kind == "pagerank" else "push"
    missing = set(SERVING_SCOPES[family]) - found
    assert not missing, (cell, kind, sorted(missing))


# the unbatched push cells, whose loop runs the sparse queue stage
QUEUE_CELLS = [cell for cell in CELLS
               if _config(cell).get("app") in ("sssp", "components")]


def _gathers(jaxpr, mult=1, whiles=0, above="", out=None):
    """Every ``gather`` of a traced program, through its calls, loops
    and branches -> [(scope path, times a pass runs it, index vectors,
    whiles around it)]: a ``scan``'s length multiplies what it holds
    (a loop of static length traces as one), a ``while`` is counted."""
    import math

    import jax

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        path = f"{above}/{eqn.source_info.name_stack}"
        name = eqn.primitive.name
        if name == "gather":
            out.append((path, mult,
                        math.prod(eqn.invars[1].aval.shape[:-1]), whiles))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _gathers(sub, mult * eqn.params["length"]
                     if name == "scan" else mult,
                     whiles + (name == "while"), path, out)
    return out


@pytest.mark.parametrize("cell", QUEUE_CELLS)
def test_a_queue_slot_fetches_a_row_a_level(cell):
    """What a queue slot fetches, counted in the traced loop of each
    push cell's form, on BOTH queue rungs: under
    ``lux_sparse_compact`` one row of splitters a level of the ranks'
    tree and the label (levels + 1 fetches a slot), under
    ``lux_sparse_expand`` (outside the budget stage's ``lux_eb``) one
    row a level of the source index's tree and three scalars (levels
    + 3); and no ``while`` of its own around any of them.  The binary
    searches these replaced fetched log2(vpad) + 1 and log2(S) + 3 a
    slot (22 and 24 on the road cell): a change that brings a step a
    bit back shows here, not only on the chip."""
    from lux_tpu.engine import frontier as fr

    eng = _engines(cell)[_config(cell)["app"]]
    jitted, args = eng.audit_programs()["converge"]
    found = _gathers(jitted.trace(*args()).jaxpr.jaxpr)
    S = eng.arrays["src_off"].shape[-1] - 1
    assert eng.arrays["src_ids"].shape[1:] == (
        sum(c for _f, c in fr.row_plan(S)), fr.ROW_FANOUT)
    allowed = {"lux_sparse_compact": len(fr.row_plan(eng.sg.vpad)) + 1,
               "lux_sparse_expand": len(fr.row_plan(S)) + 3}
    assert len(eng.queue_rungs) == 2
    for rung in range(2):
        for scope, most in allowed.items():
            mine = [(mult, n, whiles) for path, mult, n, whiles in found
                    if f"lux_q{rung}/" in path and scope in path
                    and "lux_eb" not in path and n > 1]
            slots = max(n for _mult, n, _whiles in mine)
            fetched = sum(mult * n for mult, n, _whiles in mine)
            assert fetched == most * slots, (
                cell, rung, scope, mine)
            assert len({whiles for _m, _n, whiles in mine}) == 1, (
                cell, rung, scope, mine)
