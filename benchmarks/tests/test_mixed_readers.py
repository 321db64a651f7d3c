"""The per-layer metrics ``mixed.kron20.closed`` adds, read from a
recorded span ring (three rounds of sssp / components / pagerank
turns, one idle pull boundary), and the number the cell's
no-starvation guarantee is decided by."""

import os
import types

import pytest

from benchmarks import harness
from benchmarks.readers import program_span, span_ratio
from benchmarks.runners import serve_mixed

B = "serve.boundary"


def rec(i, name, t0, t1, parent=0, **counts):
    return {"id": i, "parent": parent, "name": name, "t0": t0, "t1": t1,
            "counts": counts}


def push_turn(i, t0, kind, switch=1):
    """A 1.0 s push turn: 0.95 s segment, 0.05 s boundary."""
    b = t0 + 0.95
    return [rec(i, "serve.turn.push", t0, t0 + 1.0, kind=kind,
                switch=switch),
            rec(i + 1, B, b, t0 + 1.0, parent=i, worked=1,
                family="push"),
            rec(i + 2, B + ".fetch", b, b + 0.01, parent=i + 1,
                bytes=4096),
            rec(i + 3, B + ".unpad", b + 0.01, b + 0.04, parent=i + 1)]


def pull_turn(i, t0, worked=1):
    """A 2.0 s pull turn: 1.5 s segment; a boundary of 0.5 s that
    worked (fetch 0.05, host 0.3 in four children, place 0.1 with the
    engine's own span under it, 0.05 between the children), or one of
    0.2 s that did not."""
    b = t0 + 1.5
    out = [rec(i, "serve.turn.pull", t0, t0 + 2.0, kind="pagerank",
               switch=1)]
    if not worked:
        return out + [
            rec(i + 1, B, b, b + 0.2, parent=i, worked=0, family="pull"),
            rec(i + 2, B + ".fetch", b, b + 0.05, parent=i + 1,
                bytes=1 << 26),
            rec(i + 3, B + ".unpad", b + 0.05, b + 0.1, parent=i + 1),
            rec(i + 4, B + ".residual", b + 0.1, b + 0.2, parent=i + 1)]
    return out + [
        rec(i + 1, B, b, b + 0.5, parent=i, worked=1, family="pull"),
        rec(i + 2, B + ".fetch", b, b + 0.05, parent=i + 1,
            bytes=1 << 26),
        rec(i + 3, B + ".unpad", b + 0.05, b + 0.15, parent=i + 1),
        rec(i + 4, B + ".residual", b + 0.15, b + 0.25, parent=i + 1),
        rec(i + 5, B + ".retire", b + 0.25, b + 0.27, parent=i + 1),
        rec(i + 6, B + ".fill", b + 0.27, b + 0.28, parent=i + 1),
        rec(i + 7, B + ".pad", b + 0.28, b + 0.35, parent=i + 1),
        rec(i + 8, B + ".place", b + 0.4, b + 0.5, parent=i + 1,
            bytes=1 << 26),
        rec(i + 9, "state.place", b + 0.4, b + 0.49, parent=i + 8)]


def rounds(t0=100.0, n=3, idle_pull_round=1):
    out, i = [], 10
    for r in range(n):
        out += push_turn(i, t0, "sssp", switch=int(r > 0))
        out += push_turn(i + 10, t0 + 1.0, "components")
        out += pull_turn(i + 20, t0 + 2.0, worked=r != idle_pull_round)
        t0, i = t0 + 4.0, i + 40
    return out


@pytest.fixture
def ring(monkeypatch):
    from lux_tpu import telemetry

    def install(records):
        monkeypatch.setattr(telemetry, "spans", lambda: list(records),
                            raising=False)
    return install


def a_run(**kw):
    base = dict(t_window=100.0, spans=[("check", 200.0, 230.0)],
                events=[], trace_summary=None, config={})
    return types.SimpleNamespace(**{**base, **kw})


def metric(name, run):
    spec = harness.load_json(os.path.join(
        harness.HERE, "layer_metrics", name + ".json"))
    reader = {"program_span": program_span,
              "span_ratio": span_ratio}[spec["reader"]]
    return reader.read(spec, run)


def test_turn_metrics_by_runner_family(ring):
    ring(rounds())
    run = a_run()
    assert metric("serve.turn_ms.push", run) == pytest.approx(1000.0)
    assert metric("serve.turn_ms.pull", run) == pytest.approx(2000.0)
    assert metric("serve.turn_share.pull", run) == pytest.approx(50.0)
    # every turn but the window's first is another runner's
    assert metric("serve.switch_share", run) == pytest.approx(
        100.0 * 8 / 9)
    # the traced part: the first round and the second's push turns
    run.trace_window_s = 6.5
    assert metric("serve.turn_ms.pull", run) == pytest.approx(2000.0)
    assert program_span.read(
        dict(reader="program_span", spans=["serve.turn.*"],
             when="traced", value="count"), run) == 5.0


def test_pull_boundary_split_leaves_the_push_boundaries_out(ring):
    ring(rounds())
    run = a_run()
    pre = "serve.pull_boundary_ms."
    assert metric(pre + "total", run) == pytest.approx(500.0)
    assert metric(pre + "fetch", run) == pytest.approx(50.0)
    assert metric(pre + "host", run) == pytest.approx(300.0)
    assert metric(pre + "place", run) == pytest.approx(100.0)
    assert metric("serve.push_boundary_ms.total", run) == pytest.approx(
        50.0)
    # the push cell's own metric still averages every family: that is
    # why the mixed cell is not on its list
    assert metric("serve.boundary_ms.total", run) == pytest.approx(
        (6 * 50.0 + 2 * 500.0) / 8)


def test_a_program_without_the_spans_gives_nothing(ring):
    ring([r for r in rounds() if not r["name"].startswith("serve.turn")
          and "family" not in r["counts"]])
    run = a_run()
    for name in ("serve.turn_ms.push", "serve.turn_ms.pull",
                 "serve.turn_share.pull", "serve.switch_share",
                 "serve.pull_boundary_ms.total",
                 "serve.pull_boundary_ms.fetch",
                 "serve.push_boundary_ms.total"):
        assert metric(name, run) is None
    assert serve_mixed.turn_records(100.0, 200.0) == []


def test_starved_turns():
    kinds = ["sssp", "components", "pagerank"]
    always = lambda kind, t: True           # noqa: E731
    fair = [(float(i), kinds[i % 3]) for i in range(12)]
    assert serve_mixed.starved_turns(fair, kinds, always) == 2
    # one kind served until its queue is empty, then the next
    greedy = [(float(i), "sssp") for i in range(8)] + \
        [(8.0, "components"), (9.0, "pagerank")]
    assert serve_mixed.starved_turns(greedy, kinds, always) == 9
    # a kind without work is not waiting
    idle = lambda kind, t: kind != "pagerank" or t >= 7.0   # noqa: E731
    assert serve_mixed.starved_turns(greedy, kinds, idle) == 8
    two = [(float(i), kinds[i % 2]) for i in range(10)]
    assert serve_mixed.starved_turns(
        two, kinds, lambda kind, t: kind != "pagerank") == 1


def test_turn_records_are_the_windows_in_order(ring):
    ring(list(reversed(rounds())))
    turns = serve_mixed.turn_records(100.0, 108.0)
    assert [r["counts"]["kind"] for r in turns] == [
        "sssp", "components", "pagerank"] * 2
    assert [r["t0"] for r in turns] == sorted(r["t0"] for r in turns)
