"""The idle budget (``readers/idle_budget.py``) on synthetic rings and
device planes, on the trace recorded on the chip, and in the
rehearsal of one batch and one serving cell; and the files of the
metrics that PR 35 added."""

import os
import types

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from benchmarks.readers import idle_budget
from benchmarks.tests.test_program_readers import (  # noqa: F401
    OFF, T_WINDOW, _harness_spans, _recorded, rec, ring)

BENCH = harness.load_benchmark()
BATCH = ["pr.kron21", "bfs.kron21", "pr.kron23.mesh4", "bfs.kron23.mesh4"]
SERVING = ["ksssp.kron20.closed", "mixed.kron20.closed",
           "ksssp.kron20.open80"]
NEW = {
    "engine.idle_attributed": BATCH, "idle_pct.state_fetch": BATCH,
    "idle_pct.state_init": BATCH, "idle_pct.arrival": BATCH,
    "idle_pct.other": BATCH,
    # the BFS runner places its own state: it never calls init_state
    "state_ms.init_build": ["pr.kron21", "pr.kron23.mesh4"],
    "state_ms.init_put": ["pr.kron21", "pr.kron23.mesh4"],
    "state_ms.fetch_get": BATCH,
    "state_ms.fetch_unpad": BATCH, "idle_pct.boundary": SERVING,
    "idle_pct.segment": SERVING, "idle_pct.serve_other": SERVING}


def ps(seconds):
    return int(round(seconds * 1e12))


def plane(busy, name="/device:TPU:0"):
    """A device plane that is busy in ``busy`` (profiler seconds)."""
    busy = sorted(busy)
    return tr.DeviceSummary(
        plane=name, busy_s=sum(e - s for s, e in busy),
        first_ps=ps(busy[0][0]), last_ps=ps(busy[-1][1]), scope_s={},
        collective_s=0.0, op_s={},
        gaps=[(ps(busy[i][1]), ps(busy[i + 1][0]))
              for i in range(len(busy) - 1)])


def a_run(planes, t_trace=10.0, window=10.0, pairs=3, spans=()):
    """A traced run whose trace began at profiler second ``t_trace``:
    ``pairs`` spans recorded on both clocks give the offset."""
    both = [("solve", t_trace + 1.0 + i, t_trace + 1.5 + i)
            for i in range(pairs)] + list(spans)
    ts = tr.TraceSummary(
        devices=list(planes),
        host_spans=[(n, ps(s), ps(e)) for n, s, e in both])
    return types.SimpleNamespace(
        t_window=t_trace - OFF, _trace_t0=t_trace - OFF,
        trace_window_s=window, events=[], trace_summary=ts, config={},
        spans=[(n, s - OFF, e - OFF) for n, s, e in both])


def leaf(i, name, t0, t1, parent=0):
    """A ring record from profiler seconds."""
    return rec(i, name, t0 - OFF, t1 - OFF, parent=parent)


def pct(run, labels, **kw):
    return idle_budget.read(dict(labels=labels, **kw), run)


def test_every_idle_second_gets_one_label_and_they_sum_to_the_idle(
        ring, capsys):
    # busy 11-14 and 16-19 of the traced 10-20: head 1, gap 2, tail 1
    run = a_run([plane([(11.0, 14.0), (16.0, 19.0)])],
                spans=[("fetch", 14.0, 15.2)])
    ring([
        leaf(1, "state.init", 10.2, 11.2),          # a parent: no label
        leaf(2, "state.init.build", 10.2, 10.6, parent=1),
        leaf(3, "state.init.put", 10.6, 10.7, parent=1),
        leaf(4, "state.fetch.get", 14.0, 14.5),
        leaf(5, "state.fetch.unpad", 14.5, 15.0),
        leaf(6, "push.converge", 15.0, 15.0),       # a mark: no label
        leaf(7, "state.place", 15.5, 15.6),
    ])
    b = idle_budget.budget(run)
    want = {"after:-": 0.2, "inside:state.init.build": 0.4,
            "inside:state.init.put": 0.1, "after:state.init.put": 0.3,
            "inside:state.fetch.get": 0.5,
            "inside:state.fetch.unpad": 0.5,
            "after:state.fetch.unpad": 0.5, "inside:state.place": 0.1,
            "after:state.place": 0.4 + 1.0}
    assert b["labels"] == pytest.approx(want)
    assert b["idle"] == pytest.approx(4.0)
    assert (b["agreeing"], b["offset"]) == (4, pytest.approx(OFF))
    # points of the window: the metrics of one cell add up to its
    # idle share, 100 x (1 - 6 / 10)
    parts = [["inside:state.fetch.*"],
             ["inside:state.init.*", "inside:state.place"],
             ["after:state.init.put", "after:state.place"]]
    values = [pct(run, p) for p in parts]
    assert values == pytest.approx([10.0, 6.0, 17.0])
    other = pct(run, ["*"], **{"except": sum(parts, [])})
    assert other == pytest.approx(7.0)
    assert sum(values) + other == pytest.approx(
        100 * (1 - run.trace_summary.busy_s / run.trace_window_s))
    assert pct(run, ["inside:*"], of="idle") == pytest.approx(40.0)
    assert pct(run, ["inside:no.such"]) == 0.0
    out = capsys.readouterr().out
    assert out.count("idle budget:") == 1       # the table prints once
    assert "4 pairings agree" in out
    # the runner's own span that holds most of an ``after:`` row
    row = next(ln for ln in out.splitlines()
               if "after:state.fetch.unpad" in ln)
    assert "bench:fetch holds 0.200000 s" in row


def test_two_device_planes_are_averaged(ring):
    run = a_run([plane([(10.0, 14.0), (16.0, 20.0)], "/device:TPU:0"),
                 plane([(10.0, 15.0), (16.0, 20.0)], "/device:TPU:1"),
                 tr.DeviceSummary("/device:TPU:2", 0.0, 0, 0, {}, 0.0,
                                  {}, [])])       # unused: not counted
    ring([leaf(1, "state.fetch.get", 14.0, 15.5)])
    b = idle_budget.budget(run)
    assert b["labels"] == pytest.approx({
        "inside:state.fetch.get": (1.5 + 0.5) / 2,
        "after:state.fetch.get": 0.5})
    assert b["idle"] == pytest.approx(10.0 - run.trace_summary.busy_s)


def test_overlapping_leaves_of_two_threads_count_once(ring):
    run = a_run([plane([(10.0, 12.0), (18.0, 20.0)])])
    ring([leaf(1, "serve.boundary.unpad", 12.0, 15.0),
          leaf(2, "submit.thread", 14.0, 16.0),     # opened second
          leaf(3, "nested.in.time", 12.5, 13.0),    # under the first
          leaf(4, "serve.boundary.place", 17.0, 17.5)])
    b = idle_budget.budget(run)
    assert b["labels"] == pytest.approx({
        "inside:serve.boundary.unpad": 3.0, "inside:submit.thread": 1.0,
        "after:submit.thread": 1.0, "inside:serve.boundary.place": 0.5,
        "after:serve.boundary.place": 0.5})
    assert b["idle"] == pytest.approx(6.0)


def test_after_goes_to_the_leaf_that_closed_last(ring):
    run = a_run([plane([(10.0, 11.0), (19.0, 20.0)])])
    ring([leaf(1, "set.up", 5.0, 9.0),              # closed before
          leaf(2, "long", 12.0, 16.0),
          leaf(3, "short", 13.0, 14.0),             # closed earlier
          leaf(4, "late", 25.0, 26.0)])             # after the trace
    b = idle_budget.budget(run)
    assert b["labels"] == pytest.approx({
        "after:-": 1.0, "inside:long": 4.0, "after:long": 3.0})


def test_fewer_than_three_agreeing_pairings_give_nothing(ring, capsys):
    ring([leaf(1, "state.fetch.get", 14.0, 15.0)])
    busy = [plane([(10.0, 14.0), (16.0, 20.0)])]
    assert pct(a_run(busy, pairs=2), ["*"]) is None
    assert "idle budget" not in capsys.readouterr().out
    assert pct(a_run(busy, pairs=3), ["*"]) == pytest.approx(20.0)
    # no device plane (a CPU run), no trace, no ring: nothing, quietly
    assert pct(a_run([]), ["*"]) is None
    run = a_run(busy)
    run.trace_summary = None
    assert pct(run, ["*"]) is None
    run = a_run(busy)
    del run._trace_t0
    assert pct(run, ["*"]) is None


@pytest.mark.parametrize("closes_at", ["serve_refill", "serve.boundary"])
def test_serving_offset_pairs_the_boundary_with_what_closed_it(
        ring, closes_at):
    """The one-kind runners close ``bench:boundary`` at the
    ``serve_refill`` event, the mixed runner at the close of the
    program's ``serve.boundary`` span, a ``.place`` (about a
    millisecond, never the same twice) later: either way the pairings
    that agree to microseconds give the offset, not the looser group
    a millisecond beside it."""
    refills = [11.0, 12.0, 13.1, 14.0, 15.2]            # profiler s
    places = [1.05e-3, 0.98e-3, 1.21e-3, 1.10e-3, 1.02e-3]
    closes = [r + p for r, p in zip(refills, places)]
    ends = refills if closes_at == "serve_refill" else closes
    run = a_run([plane([(10.0, 10.5), (15.5, 20.0)])], pairs=0,
                spans=[("boundary", e - 0.04, e) for e in ends])
    run.spans = [("server_run", 1.0, 2.0)]      # only the trace has them
    run.events = [{"kind": "serve_refill", "clock": r - OFF - 3e-6}
                  for r in refills]
    ring([leaf(i, "serve.boundary", c - 0.045, c - 4e-6)
          for i, c in enumerate(closes, 1)])
    b = idle_budget.budget(run)
    assert b["agreeing"] == 5 and b["spread"] < 1e-9
    assert b["offset"] == pytest.approx(
        OFF + (3e-6 if closes_at == "serve_refill" else 4e-6), abs=1e-9)


def test_a_program_without_a_ring_reads_as_nothing(monkeypatch):
    from lux_tpu import telemetry
    monkeypatch.delattr(telemetry, "spans")
    assert pct(a_run([plane([(10.0, 14.0), (16.0, 20.0)])]),
               ["*"]) is None


def test_recorded_trace_labels_add_up_to_its_idle(ring):
    ts = _recorded()        # two spans on both clocks: a third
    ts.host_spans.append(("fetch", ts.host_spans[-1][2] + 1000,
                          ts.host_spans[-1][2] + 9000))
    dev = next(d for d in ts.devices if d.busy_s > 0)
    t0 = dev.first_ps / 1e12 - 0.010
    window = dev.last_ps / 1e12 + 0.020 - t0
    (g1s, g1e), _g2 = sorted(dev.gaps)
    run = types.SimpleNamespace(
        t_window=T_WINDOW, _trace_t0=t0 - OFF, trace_window_s=window,
        events=[], trace_summary=ts, config={},
        spans=_harness_spans(ts))
    ring([rec(1, "state.fetch.get", g1s / 1e12 - OFF - 1e-4,
              g1e / 1e12 - OFF - 1e-5)])
    b = idle_budget.budget(run)
    assert b["agreeing"] >= 3
    assert b["idle"] == pytest.approx(window - dev.busy_s, abs=1e-9)
    assert b["labels"]["after:-"] == pytest.approx(0.010, abs=1e-9)
    assert b["labels"]["inside:state.fetch.get"] == pytest.approx(
        (g1e - g1s) / 1e12 - 1e-5, abs=1e-9)


# ---- the new metrics' files ------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_loads_and_is_listed(name):
    spec = harness.load_json(os.path.join(
        harness.HERE, "layer_metrics", name + ".json"))
    assert os.path.exists(os.path.join(
        harness.HERE, "readers", spec["reader"] + ".py"))
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert entry["workloads"] == NEW[name]
    assert set(entry["workloads"]) <= cells
    assert entry["moves"] in {
        m["name"] for m in BENCH["end_to_end"]
        if all(w in m.get("workloads", cells)
               for w in entry["workloads"])}
    if spec["reader"] == "idle_budget":
        assert spec["labels"]


@pytest.mark.parametrize("cells, names", [
    (BATCH, ["idle_pct.state_fetch", "idle_pct.state_init",
             "idle_pct.arrival", "idle_pct.other"]),
    (SERVING, ["idle_pct.boundary", "idle_pct.segment",
               "idle_pct.serve_other"])])
def test_a_cells_idle_pct_metrics_label_every_second_once(cells, names):
    """Over every label a program can leave, the ``idle_pct.*`` of one
    cell pick each label exactly once."""
    specs = [harness.load_json(os.path.join(
        harness.HERE, "layer_metrics", n + ".json")) for n in names]
    for m in BENCH["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == cells
    leaves = ["state.init", "state.init.build", "state.init.put",
              "state.place", "state.fetch", "state.fetch.get",
              "state.fetch.unpad", "segment.run", "segment.count",
              "segment.recount", "serve.boundary.place",
              "serve.boundary.unpad", "serve.deliver", "-", "x.y"]
    for label in [k + n for n in leaves for k in ("inside:", "after:")]:
        picked = [idle_budget._matches(label, s["labels"])
                  and not idle_budget._matches(label,
                                               s.get("except", ()))
                  for s in specs]
        assert sum(picked) == 1, (label, picked)


@pytest.mark.parametrize("workload", ["pr.kron21", "ksssp.kron20.closed"])
def test_rehearsal_reads_the_new_metrics_or_leaves_them_out(workload):
    """On the CPU there is no device plane: the idle budget leaves its
    metrics out without an error, and the split of ``state.init`` /
    ``state.fetch`` is read from the program's ring as on the chip."""
    r = harness.run_cell(workload, 2**31 + 35, 0.5, True, rehearsal=True)
    assert r["correct"] is True
    m = r["metrics"]
    mine = {n for n, cells in NEW.items() if workload in cells}
    assert not {n for n in mine & set(m) if not n.startswith("state_ms.")}
    if workload == "pr.kron21":
        assert {"state_ms.init_build", "state_ms.init_put",
                "state_ms.fetch_get", "state_ms.fetch_unpad"} <= set(m)
        for whole, parts in (("init", ("init_build", "init_put")),
                             ("fetch", ("fetch_get", "fetch_unpad"))):
            split = sum(m["state_ms." + p]["value"] for p in parts)
            assert 0.5 * m["state_ms." + whole]["value"] < split \
                <= m["state_ms." + whole]["value"]
