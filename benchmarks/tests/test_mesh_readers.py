"""The two readers the four-chip BFS cell brought: the owner exchange's
share of the interconnect's peak and the chips' compute skew, each on a
hand-made trace summary (known bytes and seconds give the known
number), and both metric files through the harness's own lookup."""

import os
import types

import pytest

from benchmarks import harness, ici_rooflines
from benchmarks import trace_reduce as tr
from benchmarks.readers import compute_skew, ici_roofline

ICI = 1600e9                      # bits/s, benchmarks/peaks.json
SPEC = harness.load_json(os.path.join(
    harness.HERE, "layer_metrics", "exchange_ici_roofline.json"))


def device(busy_s, collective_s, plane="/device:TPU:0"):
    return tr.DeviceSummary(plane=plane, busy_s=busy_s, first_ps=0,
                            last_ps=0, scope_s={},
                            collective_s=collective_s, op_s={}, gaps=[])


def a_run(devices, nv=1 << 20, chips=4, traced=50.0):
    return types.SimpleNamespace(
        t_window=100.0, spans=[("check", 200.0, 230.0)], events=[],
        trace_window_s=traced, chips=chips, graph={"nv": nv},
        peaks={"ici_bits_per_s": ICI},
        trace_summary=None if devices is None else tr.TraceSummary(
            devices=devices, host_spans=[]))


def mark(i, t, iters, sparse_iters):
    return {"id": i, "parent": 0, "name": "push.converge", "t0": t,
            "t1": t, "counts": {"iters": iters,
                                "sparse_iters": sparse_iters}}


@pytest.fixture
def ring(monkeypatch):
    from lux_tpu import telemetry

    def install(records):
        monkeypatch.setattr(telemetry, "spans", lambda: list(records),
                            raising=False)
    return install


def test_least_bytes_are_the_other_chips_candidates():
    assert ici_rooflines.least_owner_exchange_bytes_per_chip(
        1 << 23, 4) == 25_165_824           # 4 B x 8.4 M x 3 / 4
    assert ici_rooflines.least_owner_exchange_bytes_per_chip(
        1 << 23, 1) == 0


def test_known_bytes_and_seconds_give_the_known_share(ring):
    """Two traced searches with 3 + 2 dense iterations of 3,145,728
    bytes a chip = 15,728,640 B = 78.6432 us at 200 GB/s, over a mean
    of 0.4 ms of collectives: 19.6608%.  The search after the traced
    part and the one in the check do not count."""
    ring([mark(1, 110.0, 7, 4), mark(2, 120.0, 3, 1),
          mark(3, 160.0, 9, 0), mark(4, 210.0, 9, 0)])
    run = a_run([device(1.0, 0.0003), device(1.0, 0.0005),
                 device(0.0, 0.0, "/device:TPU:2")])
    assert ici_roofline.read(SPEC, run) == pytest.approx(19.6608)


def test_no_dense_iteration_gives_no_value_not_zero(ring):
    ring([mark(1, 110.0, 4, 4)])
    assert ici_roofline.read(SPEC, a_run([device(1.0, 0.001)])) is None


def test_nothing_to_read_is_none(ring, monkeypatch):
    ring([mark(1, 110.0, 7, 4)])
    assert ici_roofline.read(SPEC, a_run(None)) is None
    assert ici_roofline.read(SPEC, a_run([device(1.0, 0.0)])) is None
    ring([])                     # a program that leaves no such mark
    assert ici_roofline.read(SPEC, a_run([device(1.0, 0.001)])) is None
    from lux_tpu import telemetry
    monkeypatch.delattr(telemetry, "spans")       # no ring at all
    assert ici_roofline.read(SPEC, a_run([device(1.0, 0.001)])) is None
    assert compute_skew.read({}, a_run(None)) is None
    assert compute_skew.read({}, a_run([device(0.0, 0.0)])) is None


def test_skew_of_four_chips_with_known_seconds():
    """Own work 4.0, 4.0, 4.0 and 5.0 s (busy less collectives): mean
    4.25, the slowest 0.75 over it: 17.647%."""
    run = a_run([device(4.5, 0.5), device(4.1, 0.1), device(4.0, 0.0),
                 device(5.2, 0.2)])
    assert compute_skew.read({}, run) == pytest.approx(100 * 0.75 / 4.25)


def test_one_chip_has_no_skew():
    assert compute_skew.read({}, a_run([device(3.0, 0.0)], chips=1)) == 0.0


@pytest.mark.parametrize("name", ["exchange_ici_roofline",
                                  "mesh.compute_skew",
                                  "scope_ms.sparse_exchange"])
def test_the_metric_is_the_new_cells_alone_and_moves_its_gteps(name):
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["workloads"] == ["bfs.kron23.mesh4"]
    assert entry["moves"] == "gteps_per_chip"
    assert entry in harness.metrics_for(bench, "per_layer",
                                        "bfs.kron23.mesh4")
