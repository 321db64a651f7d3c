"""The readers of the program's span ring (``program_span``,
``program_count``, ``idle_attributed``) on a synthetic ring, and the
idle attribution on the trace recorded on the chip
(``recorded_v5e.xplane.pb.gz``: its device gaps and ``bench:`` spans
are real, the ring laid over them is made here)."""

import gzip
import os
import types

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import (idle_attributed, program_count,
                                program_span)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb.gz")
B = "serve.boundary"


def rec(i, name, t0, t1, parent=0, **counts):
    return {"id": i, "parent": parent, "name": name, "t0": t0, "t1": t1,
            "counts": counts}


def a_run(t_window=100.0, spans=(), events=(), trace_summary=None):
    return types.SimpleNamespace(
        t_window=t_window, spans=list(spans), events=list(events),
        trace_summary=trace_summary, config={})


@pytest.fixture
def ring(monkeypatch):
    """Install a synthetic ring as ``lux_tpu.telemetry.spans()``."""
    from lux_tpu import telemetry

    def install(records):
        monkeypatch.setattr(telemetry, "spans", lambda: list(records),
                            raising=False)
    return install


# set-up before 100 s, a window of three boundaries after it, a
# check span from 200 s on
RING = [
    rec(1, "relabel", 10.0, 14.0),
    rec(2, "relabel.deal", 11.0, 12.0, parent=1),
    rec(4, "build.pair_plan", 21.0, 27.0, pair_edges=30,
        residual_edges=10),
    rec(5, "jit.trace", 40.0, 43.0),
    rec(6, "jit.trace", 41.0, 42.0),        # nested in the one above
    rec(7, "jit.compile", 43.0, 45.0),
    # window: worked, idle, worked
    rec(10, B, 110.0, 110.4, worked=1),
    rec(11, B + ".counts", 110.0, 110.02, parent=10),
    rec(12, B + ".fetch", 110.02, 110.10, parent=10, bytes=84),
    rec(13, B + ".place", 110.3, 110.4, parent=10, bytes=84),
    rec(14, "state.place", 110.3, 110.39, parent=13, bytes=84),
    rec(20, B, 120.0, 120.01, worked=0),
    rec(21, B + ".counts", 120.0, 120.01, parent=20),
    rec(30, B, 130.0, 130.6, worked=1),
    rec(31, B + ".counts", 130.0, 130.04, parent=30),
    rec(32, B + ".fetch", 130.04, 130.20, parent=30, bytes=84),
    rec(40, "push.converge", 140.0, 140.0, iters=7, sparse_iters=4),
    rec(41, "push.converge", 150.0, 150.0, iters=3, sparse_iters=1),
    # after the window: the check's own work
    rec(50, "jit.compile", 210.0, 211.0),
    rec(51, B, 205.0, 205.5, worked=1),
]
RUN = dict(t_window=100.0, spans=[("check", 200.0, 230.0)])


def spec(**kw):
    return dict(reader="program_span", **kw)


def test_seconds_is_the_union_so_nested_records_count_once(ring):
    ring(RING)
    run = a_run(**RUN)
    assert program_span.read(
        spec(spans=["relabel"], when="setup"), run) == pytest.approx(4.0)
    assert program_span.read(
        spec(spans=["jit.*"], when="setup"), run) == pytest.approx(5.0)
    assert program_span.read(
        spec(spans=["relabel.deal", "relabel"], when="setup"),
        run) == pytest.approx(4.0)


def test_setup_and_window_are_split_at_the_window_and_the_check(ring):
    ring(RING)
    run = a_run(**RUN)
    count = spec(spans=["jit.compile"], when="window", value="count")
    assert program_span.read(count, run) == 0.0      # 7 before, 50 after
    assert program_span.read(dict(count, when="setup"), run) == 1.0
    assert program_span.read(
        spec(spans=[B], when="window", value="count"), run) == 3.0
    # a runner that kept events for its window ends the window there
    run = a_run(events=[{"kind": "serve_refill", "clock": 125.0}], **RUN)
    assert program_span.read(
        spec(spans=[B], when="window", value="count"), run) == 2.0


def test_traced_is_the_part_of_the_window_the_profiler_saw(ring):
    ring(RING)
    run = a_run(**RUN)
    count = spec(spans=[B], when="traced", value="count")
    assert program_span.read(count, run) == 3.0     # nothing traced
    run.trace_window_s = 25.0       # 100..125: two boundaries ended
    assert program_span.read(count, run) == 2.0
    run.trace_window_s = 10.2       # the first began AND ended inside
    assert program_span.read(count, run) == 0.0
    run.trace_window_s = 10.5
    assert program_span.read(count, run) == 1.0


def test_mean_where_and_per(ring):
    ring(RING)
    run = a_run(**RUN)
    total = spec(spans=[B], when="window", where={"worked": 1},
                 value="mean_ms")
    assert program_span.read(total, run) == pytest.approx(500.0)
    fetch = spec(spans=[B + ".counts", B + ".fetch"], per=B,
                 when="window", where={"worked": 1})
    # (20 + 80 + 40 + 160) ms over the two worked boundaries; the idle
    # boundary's .counts is not theirs
    assert program_span.read(fetch, run) == pytest.approx(150.0)
    place = spec(spans=[B + ".place"], per=B, when="window",
                 where={"worked": 1})
    assert program_span.read(place, run) == pytest.approx(50.0)
    assert program_span.read(
        spec(spans=["no.such"], when="window", value="mean_ms"),
        run) is None
    assert program_span.read(
        spec(spans=[B + ".place"], per="no.such", when="window"),
        run) is None


def test_program_count_sums_and_ratios(ring):
    ring(RING)
    run = a_run(**RUN)
    share = dict(reader="program_count", spans=["push.converge"],
                 when="window", field="sparse_iters", over="iters",
                 percent=True)
    assert program_count.read(share, run) == pytest.approx(50.0)
    cover = dict(reader="program_count", spans=["build.pair_plan"],
                 when="setup", field="pair_edges",
                 over=["pair_edges", "residual_edges"], percent=True)
    assert program_count.read(cover, run) == pytest.approx(75.0)
    moved = dict(reader="program_count", spans=[B + ".fetch"],
                 when="window", field="bytes")
    assert program_count.read(moved, run) == 168.0
    assert program_count.read(dict(share, over="no_such"), run) is None
    assert program_count.read(dict(share, spans=["nope"]), run) is None


def test_a_program_without_a_ring_reads_as_nothing(monkeypatch):
    from lux_tpu import telemetry
    monkeypatch.delattr(telemetry, "spans")
    run = a_run(**RUN)
    assert program_span.read(
        spec(spans=["jit.compile"], when="window", value="count"),
        run) is None
    assert program_count.read(
        dict(spans=["push.converge"], when="window", field="iters"),
        run) is None
    assert idle_attributed.read(
        {}, a_run(trace_summary=_recorded(), **RUN)) is None


# ---- idle attribution on the recorded trace --------------------------

def _recorded():
    with gzip.open(RECORDED, "rb") as f:
        return tr.reduce_planes(tr.parse_xspace(f.read()))


# profiler seconds minus perf_counter seconds: a trace starts near 0,
# perf_counter is some large time since boot
OFF = -12345.678901
T_WINDOW = 12345.0           # before the trace, after load_layout


def _harness_spans(ts):
    """The harness's own record of the spans the trace holds, on
    perf_counter, plus one from before the trace began."""
    return [("load_layout", 1.0, 2.0)] + [
        (n, s / 1e12 - OFF, e / 1e12 - OFF) for n, s, e in ts.host_spans]


def test_offset_is_recovered_from_spans_recorded_on_both_clocks():
    ts = _recorded()
    run = a_run(t_window=T_WINDOW, spans=_harness_spans(ts),
                trace_summary=ts)
    assert idle_attributed.clock_offset(run) == pytest.approx(
        OFF, abs=1e-9)
    # nothing recorded on both clocks: no offset, never an assumed one
    run = a_run(t_window=T_WINDOW, spans=[("check", 5.0, 6.0)],
                trace_summary=ts)
    assert idle_attributed.clock_offset(run) is None


def test_gaps_go_to_the_leaf_span_that_covers_them(ring, capsys):
    ts = _recorded()
    (g1s, g1e), (g2s, g2e) = sorted(ts.devices[0].gaps)
    half = (g2s + g2e) // 2

    def pc(ps):
        return ps / 1e12 - OFF

    ring([
        # a parent over everything: not a leaf, attributes nothing
        rec(1, "solve.outer", pc(g1s) - 1e-3, pc(g2e) + 1e-3),
        rec(2, "state.fetch", pc(g1s) - 1e-4, pc(g1e) + 1e-5, parent=1),
        rec(3, "state.place", pc(g2s), pc(half), parent=1),
        rec(4, "push.converge", pc(half), pc(half), parent=1),  # a mark
    ])
    run = a_run(t_window=T_WINDOW, spans=_harness_spans(ts),
                trace_summary=ts)
    share = idle_attributed.read({}, run)
    idle = (g1e - g1s) + (g2e - g2s)
    want = 100.0 * ((g1e - g1s) + (half - g2s)) / idle
    assert share == pytest.approx(want, rel=1e-6)
    assert 80 < share < 100
    out = capsys.readouterr().out
    assert "state.fetch" in out and "state.place" in out
    assert "solve.outer" not in out
    # 9% of the idle time lies under solve.outer, between its children
    assert "between the children of a span" in out
    assert "outside every span         " in out


def test_serving_offset_survives_a_boundary_without_a_refill():
    """``bench:boundary`` ends against ``serve_refill`` clocks: one
    boundary more than refills (an idle one, closed by the next
    ``segment``) shifts a pairing by order, not the cluster."""
    ends = [10.0, 11.0, 12.1, 13.0, 14.2]               # profiler seconds
    host = [("boundary", int((e - 0.4) * 1e12), int(e * 1e12))
            for e in ends]
    dev = tr.DeviceSummary(plane="/device:TPU:0", busy_s=3.0,
                           first_ps=int(9.0e12), last_ps=int(15e12),
                           scope_s={}, collective_s=0.0, op_s={},
                           gaps=[(int(9.6e12), int(10.0e12))])
    ts = tr.TraceSummary(devices=[dev], host_spans=host)
    refills = [{"kind": "serve_refill", "clock": e - OFF + 3e-6}
               for e in (ends[0], ends[1], ends[3], ends[4])]
    run = a_run(t_window=9.0 - OFF, spans=[("server_run", 1.0, 2.0)],
                events=refills, trace_summary=ts)
    assert idle_attributed.clock_offset(run) == pytest.approx(
        OFF, abs=1e-5)


def test_nothing_to_read_is_none(ring):
    ring(RING)
    assert idle_attributed.read({}, a_run(**RUN)) is None   # no trace
    ts = _recorded()
    assert idle_attributed.read(
        {}, a_run(t_window=T_WINDOW, spans=[], trace_summary=ts)) is None


# ---- every cell's traced rehearsal reports its program metrics -------

from benchmarks import harness  # noqa: E402

BENCH = harness.load_benchmark()
NEEDS_A_DEVICE_PLANE = {"serve.idle_attributed"}


def _program_metrics(workload):
    """The per-layer metrics of a cell that read the program's ring."""
    out = []
    for m in harness.metrics_for(BENCH, "per_layer", workload):
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".json"))
        if spec["reader"] in ("program_span", "program_count",
                              "idle_attributed"):
            out.append(m["name"])
    return out


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_traced_rehearsal_reports_every_program_metric(workload):
    r = harness.run_cell(workload, 2**31 + 24, 0.5, True, rehearsal=True)
    assert r["correct"] is True
    want = set(_program_metrics(workload)) - NEEDS_A_DEVICE_PLANE
    assert want and want <= set(r["metrics"]), want - set(r["metrics"])
    compiles = [n for n in want if n.endswith("jit_compiles_in_window")
                or n == "jit.compiles_in_window"]
    assert len(compiles) == 1
    assert r["metrics"][compiles[0]]["value"] == 0.0
    if workload == "bfs.kron21":
        assert 0 < r["metrics"]["engine.sparse_iter_share"]["value"] < 100
    if workload == "ksssp.kron20.closed":
        m = r["metrics"]
        parts = sum(m[f"serve.boundary_ms.{k}"]["value"]
                    for k in ("fetch", "host", "place"))
        assert parts <= m["serve.boundary_ms.total"]["value"]
