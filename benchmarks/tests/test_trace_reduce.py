"""The reduction from trace to numbers, on a hand-built XSpace (known
answers, collectives included) and on a trace recorded on the chip
(``recorded_v5e.xplane.pb.gz``, made by ``record_trace.py``: one
PageRank solve and one BFS at scale 10 on one TPU v5e, PR 23)."""

import gzip
import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb.gz")


# ---- a tiny XSpace writer (wire format) ------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field, value):
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines, metas, stat_names):
    """lines: {line name: [(meta id, offset ps, duration ps)]};
    metas: {id: (name, {stat name: str})}."""
    sid = {n: i + 1 for i, n in enumerate(stat_names)}
    buf = _f(2, name)
    for lname, events in lines.items():
        line = _f(2, lname) + _f(3, 1000)           # timestamp 1000 ns
        for mid, off, dur in events:
            line += _f(4, _f(1, mid) + _f(2, off) + _f(3, dur))
        buf += _f(3, line)
    for mid, (mname, stats) in metas.items():
        md = _f(1, mid) + _f(2, mname)
        for sname, sval in stats.items():
            md += _f(5, _f(1, sid[sname]) + _f(5, sval))
        buf += _f(4, _f(1, mid) + _f(2, md))
    for sname, i in sid.items():
        buf += _f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, sname)))
    return _f(1, buf)


def _space():
    ps = 10**6                                       # one microsecond
    metas = {
        1: ("%while.1 = (f32[8]) while(...)", {"tf_op": "jit(run)/while"}),
        2: ("%fusion.1 = f32[8] fusion(f32[8] %p)",
            {"tf_op": "jit(run)/while/body/lux_pagerank/"
                      "vmap(lux_gather_reduce)/add"}),
        3: ("%fusion.2 = f32[8] fusion(f32[8] %p)",
            {"tf_op": "jit(run)/while/body/lux_pagerank/lux_apply/mul"}),
        4: ("%all-gather.3 = f32[32] all-gather(f32[8] %p)",
            {"tf_op": "jit(run)/while/body/lux_pagerank/lux_exchange/"
                      "all_gather"}),
        5: ("%copy.9 = f32[8] copy(f32[8] %p)", {}),
        6: ("jit_run(1)", {}),
    }
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [(6, 0, 100 * ps), (6, 150 * ps, 50 * ps)],
        "XLA Ops": [
            (1, 0, 100 * ps),            # the while spans its body
            (2, 10 * ps, 40 * ps),       # gather_reduce
            (3, 50 * ps, 10 * ps),       # apply
            (4, 60 * ps, 20 * ps),       # collective
            (5, 150 * ps, 50 * ps),      # unscoped, second program
        ]}, metas, ["tf_op"])
    host = _plane("/host:CPU", {
        "python3": [(1, 0, 120 * ps), (2, 120 * ps, 100 * ps)]},
        {1: ("bench:solve", {}), 2: ("bench:fetch", {})}, [])
    return dev + host


def test_busy_is_the_union_not_the_sum():
    s = tr.reduce_planes(tr.parse_xspace(_space()))
    (d,) = s.devices
    # nested ops do not count twice; the 50 us between the programs
    # is idle
    assert d.busy_s == pytest.approx(150e-6)
    assert s.busy_s == pytest.approx(150e-6)
    assert [round((e - b) / 1e6) for b, e in d.gaps] == [50]


def test_time_goes_to_scopes_by_self_time():
    s = tr.reduce_planes(tr.parse_xspace(_space()))
    assert s.scope_seconds("lux_gather_reduce") == pytest.approx(40e-6)
    assert s.scope_seconds("lux_apply") == pytest.approx(10e-6)
    assert s.scope_seconds("lux_exchange") == pytest.approx(20e-6)
    # every op under the program's scope, and nothing dropped
    assert s.scope_seconds("lux_pagerank") == pytest.approx(70e-6)
    (d,) = s.devices
    assert d.scope_s["unscoped"] == pytest.approx(80e-6)  # while + copy
    assert sum(d.scope_s.values()) == pytest.approx(d.busy_s)


def test_collectives_are_found():
    s = tr.reduce_planes(tr.parse_xspace(_space()))
    assert s.collective_seconds() == pytest.approx(20e-6)


def test_gaps_are_named_by_the_host_span_over_them():
    s = tr.reduce_planes(tr.parse_xspace(_space()))
    assert s.top_gaps(3) == [["fetch", pytest.approx(50e-6)]]


def test_scope_chain_and_attribution():
    assert tr.scope_chain(
        "jit(inner)/lux_sssp/while/body/cond/branch_0_fun/lux_dense/"
        "vmap(lux_reduce)/eq") == "lux_sssp/lux_dense/lux_reduce"
    assert tr.attribute("lux_a/lux_b", {"lux_a"}) == "lux_a/lux_b"
    assert tr.attribute("lux_a/lux_b", {"lux_a/lux_c"}) == "mixed"
    assert tr.attribute("", {"lux_a/lux_c"}) == "lux_a/lux_c"
    assert tr.attribute("", ()) == "unscoped"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this tree")
def test_recorded_chip_trace():
    with gzip.open(RECORDED, "rb") as f:
        s = tr.reduce_planes(tr.parse_xspace(f.read()))
    (d,) = [d for d in s.devices if d.busy_s > 0]
    window = (d.last_ps - d.first_ps) / 1e12
    assert 0 < d.busy_s <= window
    # the self times add up to the union (a program starts a little
    # before its first op): nothing dropped, nothing counted twice
    assert sum(d.scope_s.values()) == pytest.approx(d.busy_s, rel=1e-2)
    # both engines' scopes are found through the tf_op metadata
    assert s.scope_seconds("lux_pagerank") > 0
    assert s.scope_seconds("lux_sssp") > 0
    assert s.scope_seconds("lux_dense") > 0
    assert s.collective_seconds() == 0            # one chip
    names = {n for n, _s, _e in s.host_spans}
    assert {"solve", "search"} <= names
    assert all(g[1] > 0 for g in s.top_gaps(5))
