"""lux_tpu/comms.py: the communication observatory (round 19).

The acceptance surface: the per-collective byte ledger of every
exchange mode agrees BITWISE with the independent NumPy message-count
oracle at ndev 1/2/8 (batched B > 1 included), a deliberately
mis-counted synthetic program raises the typed CommLedgerError, the
decompose comm verdict rides the telemetry trail through
events_summary cleanly, and the CLI round-trips.
"""

import functools
import json
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lux_tpu import comms, observe, scalemodel, telemetry
from lux_tpu.graph import Graph

REPO = Path(__file__).resolve().parent.parent


def mk_graph(nv=256, ne=2048, weighted=False, seed=0):
    r = np.random.default_rng(seed)
    src = r.integers(0, nv, ne)
    dst = r.integers(0, nv, ne)
    w = (r.integers(1, 6, ne).astype(np.float32) if weighted
         else None)
    return Graph.from_edges(src, dst, nv, weights=w)


def mesh_of(n):
    from lux_tpu.parallel.mesh import make_mesh
    return make_mesh(n)


# ---------------------------------------------------------------------
# hop convention + tier classification

def test_shipped_bytes_convention():
    # ring algorithms, integer arithmetic (module docstring table)
    assert comms.shipped_bytes("ppermute", 1024, 4) == 1024
    assert comms.shipped_bytes("all_gather", 1000, 4) == 3000
    assert comms.shipped_bytes("reduce_scatter", 1024, 4) == 768
    assert comms.shipped_bytes("psum_scatter", 1024, 4) == 768
    assert comms.shipped_bytes("all_to_all", 1024, 4) == 768
    assert comms.shipped_bytes("psum", 4, 2) == 4      # RS + AG
    for prim in ("ppermute", "all_gather", "psum"):
        assert comms.shipped_bytes(prim, 4096, 1) == 0
    with pytest.raises(ValueError):
        comms.shipped_bytes("broadcast", 4, 2)


def test_mesh_tier_slice_topology():
    def fake_mesh(slice_ids):
        devs = np.array([types.SimpleNamespace(slice_index=s)
                         for s in slice_ids], dtype=object)
        return types.SimpleNamespace(devices=devs)

    assert comms.mesh_tier(None) == "local"
    assert comms.mesh_tier(fake_mesh([0, 0, 0, 0])) == "ici"
    assert comms.mesh_tier(fake_mesh([0, 0, 1, 1])) == "dcn"
    # CPU devices carry no slice_index: one slice, ici
    assert comms.mesh_tier(mesh_of(2)) == "ici"


# ---------------------------------------------------------------------
# ledger vs oracle, every exchange mode, ndev 1 / 2 / 8

def _mode_engines():
    """(label, engine) covering every exchange family the ISSUE
    names: owner psum_scatter / all_to_all / fused ring / pagemajor
    routing, the gather all_gather, sparse-queue branches, batched
    B > 1 — at ndev 1, 2 and 8."""
    from lux_tpu.apps import components, pagerank, sssp

    g = mk_graph()
    gs = mk_graph(512, 4096, seed=2)
    out = []
    out.append(("owner_sum_ndev1",
                pagerank.build_engine(g, num_parts=4,
                                      exchange="owner")))
    out.append(("gather_mesh2",
                pagerank.build_engine(g, num_parts=2,
                                      mesh=mesh_of(2))))
    out.append(("owner_sum_mesh2",
                pagerank.build_engine(g, num_parts=2, mesh=mesh_of(2),
                                      exchange="owner")))
    out.append(("owner_sum_mesh8",
                pagerank.build_engine(gs, num_parts=8,
                                      mesh=mesh_of(8),
                                      exchange="owner")))
    out.append(("owner_a2a_mesh2",
                components.build_engine(g, num_parts=2,
                                        mesh=mesh_of(2),
                                        exchange="owner")))
    out.append(("owner_a2a_dense_mesh2",
                components.build_engine(g, num_parts=2,
                                        mesh=mesh_of(2),
                                        exchange="owner",
                                        enable_sparse=False)))
    out.append(("owner_ring_mesh2",
                components.build_engine(g, num_parts=2,
                                        mesh=mesh_of(2),
                                        exchange="owner",
                                        owner_minmax_fused=True)))
    out.append(("owner_ring_mesh8",
                components.build_engine(gs, num_parts=8,
                                        mesh=mesh_of(8),
                                        exchange="owner",
                                        owner_minmax_fused=True)))
    out.append(("owner_pagemajor_mesh2",
                pagerank.build_engine(g, num_parts=2, mesh=mesh_of(2),
                                      exchange="owner",
                                      gather="pagemajor")))
    out.append(("sparse_gather_mesh2",
                sssp.build_engine(g, 0, num_parts=2,
                                  mesh=mesh_of(2))))
    # batched B > 1: the trailing query axis rides every payload
    out.append(("owner_sum_batched_mesh2",
                pagerank.build_engine(g, num_parts=2, mesh=mesh_of(2),
                                      sources=[0, 3, 7, 11],
                                      exchange="owner")))
    out.append(("ksssp_batched_mesh2",
                sssp.build_engine(g, num_parts=2, mesh=mesh_of(2),
                                  sources=[0, 3, 7, 11])))
    # a symmetric graph builds the bottom-up step: its choice's four
    # scalars before the cond, the pulled buffer's combine in every
    # queue rung (min and max programs)
    from lux_tpu.graph import Graph
    src, dst = g.edge_arrays()
    sym = Graph.from_edges(np.concatenate([src, dst]),
                           np.concatenate([dst, src]), g.nv)
    out.append(("pull_step_min_mesh2",
                sssp.build_engine(sym, 0, num_parts=2,
                                  mesh=mesh_of(2))))
    out.append(("pull_step_min_owner_mesh8",
                sssp.build_engine(sym, 0, num_parts=8, mesh=mesh_of(8),
                                  exchange="owner")))
    out.append(("pull_step_max_mesh2",
                components.build_engine(sym, num_parts=2,
                                        mesh=mesh_of(2))))
    assert all(e.pull for _l, e in out[-3:])
    return out


@pytest.mark.parametrize("label_eng", _mode_engines(),
                         ids=lambda le: le[0])
def test_ledger_bitwise_equals_oracle(label_eng):
    label, eng = label_eng
    # ledger_for(check=True) raises CommLedgerError on ANY
    # disagreement; the explicit bitwise assertions pin the contract
    led = comms.ledger_for(eng, where=label)
    oracle = comms.oracle_for(eng)
    ob, om = comms._oracle_totals(oracle)
    assert led.bytes_per_iter == ob
    assert led.messages == om
    assert sorted(e.key() for e in led.entries) == \
        sorted(e.key() for e in oracle)
    if eng.ndev == 1:
        assert led.bytes_per_iter == 0 and not led.entries
        assert led.tier == "local"
    else:
        assert led.bytes_per_iter > 0
        assert led.tier == "ici"
        assert led.bytes_per_edge == pytest.approx(
            led.bytes_per_iter * eng.ndev / eng.sg.ne)


def test_mode_shapes_pinned():
    """The per-mode collective shapes of record: ring = ndev-1
    ppermute hops of the per-device chunk; sum = one reduce_scatter
    of the full contribution table; pagemajor = one all_to_all of
    [P_local, P, Mg, 128] message rows."""
    from lux_tpu.apps import components, pagerank

    g = mk_graph(512, 4096, seed=2)
    ring = components.build_engine(g, num_parts=8, mesh=mesh_of(8),
                                   exchange="owner",
                                   owner_minmax_fused=True)
    led = comms.ledger_for(ring)
    hops = [e for e in led.entries if e.prim == "ppermute"]
    assert len(hops) == 7                       # ndev - 1
    assert all(e.shape[0] == 1 for e in hops)   # [P/ndev, ntw]
    pm = pagerank.build_engine(mk_graph(), num_parts=2,
                               mesh=mesh_of(2), exchange="owner",
                               gather="pagemajor")
    led = comms.ledger_for(pm)
    (a2a,) = [e for e in led.entries if e.prim == "all_to_all"]
    Mg = int(pm.page_plan.route)
    assert a2a.shape == (1, 2, Mg, 128)
    assert a2a.shipped_bytes == a2a.payload_bytes // 2


def test_engine_comm_ledger_method():
    from lux_tpu.apps import pagerank
    eng = pagerank.build_engine(mk_graph(), num_parts=2,
                                mesh=mesh_of(2), exchange="owner")
    led = eng.comm_ledger()
    assert led.bytes_per_iter > 0
    with pytest.raises(KeyError, match="no registered program"):
        eng.audit_variant("definitely_not_a_variant")


def test_full_audit_matrix_ledgers():
    """The acceptance command's body: one oracle-checked ledger per
    audit-matrix config (the same engines the repo-wide audit
    traces), every mesh owner config shipping real bytes."""
    out = comms.run_matrix(emit_events=False)
    assert len(out) >= 30
    by = {d["config"]: d for d in out}
    assert by["pagerank_mesh2_owner_sum"]["bytes_per_iter"] > 0
    assert by["pagerank_np2_gather"]["bytes_per_iter"] == 0
    assert all(d["oracle_ok"] for d in out)
    # single-device configs ship nothing; mesh owner/gather configs
    # always ship something
    for d in out:
        if d["ndev"] == 1:
            assert d["bytes_per_iter"] == 0 and d["tier"] == "local"
        elif d["exchange"] in ("owner", "gather"):
            assert d["bytes_per_iter"] > 0


# ---------------------------------------------------------------------
# typed errors: the mis-counted synthetic program (test-pinned)

def _synthetic_ledger(n_collectives):
    mesh = mesh_of(2)
    P = jax.sharding.PartitionSpec

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("parts"),
                       out_specs=P("parts"))
    def prog(x):
        for _ in range(n_collectives):
            x = jax.lax.psum_scatter(
                x, "parts", scatter_dimension=0, tiled=True)
            x = jnp.concatenate([x, x], axis=0)
        return x

    closed = prog.trace(
        jax.ShapeDtypeStruct((8, 64), jnp.float32)).jaxpr
    return comms.ledger_of_jaxpr(closed, ndev=2, where="synthetic")


def test_miscounted_program_raises_typed_error():
    """A program running TWO reduce-scatters where the oracle expects
    one is exactly the double-exchange bug class the ledger exists to
    catch — typed CommLedgerError, with the disagreement itemized."""
    led2 = _synthetic_ledger(2)
    oracle1 = [e for e in led2.entries][:1]
    with pytest.raises(comms.CommLedgerError) as ei:
        comms.cross_check(led2, oracle1, where="synthetic")
    assert "disagrees with the NumPy oracle" in str(ei.value)
    assert ei.value.details
    # the honest single-collective program passes its own entries
    led1 = _synthetic_ledger(1)
    comms.cross_check(led1, list(led1.entries), where="synthetic")


def test_byte_total_mismatch_raises():
    led = _synthetic_ledger(1)
    wrong = [comms.CollectiveEntry(
        prim=e.prim, shape=e.shape, dtype=e.dtype,
        payload_bytes=e.payload_bytes,
        shipped_bytes=e.shipped_bytes + 4, mult=e.mult,
        tier=e.tier, branch=e.branch) for e in led.entries]
    with pytest.raises(comms.CommLedgerError, match="bytes_per_iter"):
        comms.cross_check(led, wrong)


def test_count_only_multiset_mismatch_raises():
    """A count-only disagreement with IDENTICAL byte totals (ledger
    2x key A vs oracle 1x A + 1x same-byte key B) must still raise —
    the multiset contract compares per-key counts, not totals."""
    led = _synthetic_ledger(2)
    e = led.entries[0]
    swapped = comms.CollectiveEntry(
        prim=e.prim, shape=e.shape, dtype="int32",
        payload_bytes=e.payload_bytes,
        shipped_bytes=e.shipped_bytes, mult=e.mult, tier=e.tier)
    oracle = [e, swapped]
    assert comms._oracle_totals(oracle)[0] == led.bytes_per_iter
    with pytest.raises(comms.CommLedgerError) as ei:
        comms.cross_check(led, oracle)
    assert "traced program carries 2x" in str(ei.value)


def test_audit_spec_contradiction_raises(monkeypatch):
    """A ledger whose eqn set violates the collective-schedule
    expectations (here: the auditor told to demand a ring the sum
    program does not run) raises the typed error — the two
    subsystems read one registry and must agree."""
    from lux_tpu import audit
    from lux_tpu.apps import pagerank

    eng = pagerank.build_engine(mk_graph(), num_parts=2,
                                mesh=mesh_of(2), exchange="owner")
    real = audit.engine_spec

    def fake_spec(e, aval):
        return audit.ProgramSpec(
            **{**real(e, aval).__dict__, "ppermute_hops": 1})

    monkeypatch.setattr(audit, "engine_spec", fake_spec)
    with pytest.raises(comms.CommLedgerError, match="ppermute"):
        comms.ledger_for(eng)


# ---------------------------------------------------------------------
# measured link calibration + scalemodel feed

def test_link_registry_and_projection_feed():
    assert scalemodel.link_bytes_per_s("ici") > 0
    assert scalemodel.link_bytes_per_s("dcn") == pytest.approx(
        scalemodel.link_bytes_per_s("ici")
        / scalemodel.DCN_THINNESS_MODEL)
    with pytest.raises(ValueError):
        scalemodel.link_bytes_per_s("local")
    with pytest.raises(ValueError):
        scalemodel.set_measured_link("ici", -1.0)
    try:
        scalemodel.set_measured_link("ici", 1e9)
        assert scalemodel.measured_link("ici") == 1e9
        # project_pull now prices comm from the measured figure
        slow = scalemodel.project_pull(1 << 24, 1 << 20, 8)
        scalemodel._MEASURED_LINKS.clear()
        fast = scalemodel.project_pull(1 << 24, 1 << 20, 8)
        assert slow.comm_s > fast.comm_s
    finally:
        scalemodel._MEASURED_LINKS.clear()


def test_calibrate_links_cpu_mesh_records_but_never_feeds():
    import itertools
    clk = itertools.count()
    scalemodel._MEASURED_LINKS.clear()
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        links = observe.calibrate_links(
            payload_elems=(1 << 10,), repeats=2,
            clock=lambda: next(clk) * 1e-3)
    assert "ici" in links
    rec = links["ici"]
    assert rec["bytes_per_s"] > 0
    assert rec["prim"] == "ppermute"
    assert rec["fed_scalemodel"] is False       # CPU: labeled, not fed
    assert scalemodel.measured_link("ici") is None
    assert observe.link_rate("ici") == rec["bytes_per_s"]
    kinds = [e["kind"] for e in ev.events]
    assert "link_calibration" in kinds
    observe._LINKS.clear()


def test_tampered_comm_ledger_event_fails_summary(tmp_path):
    """events_summary FAILS a comm_ledger whose breakdown contradicts
    the audit eqn set it carries (the established contradiction-check
    pattern)."""
    evp = tmp_path / "ev.jsonl"
    good = {"t": 1.0, "tm": 1.0, "kind": "comm_ledger",
            "app": "pagerank", "exchange": "owner", "ndev": 2,
            "ne": 2048, "bytes_per_iter": 1024, "bytes_per_edge": 1.0,
            "messages": 1, "tier": "ici",
            "per_collective": [
                {"prim": "reduce_scatter", "branch": "", "count": 1,
                 "eqns": 1, "shipped_bytes": 1024,
                 "payload_bytes": 2048, "tier": "ici"}],
            "audit_eqns": {"reduce_scatter": 1}, "verdict": "ok"}
    evp.write_text(json.dumps(good) + "\n")
    r = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "events_summary.py"), str(evp)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    bad = dict(good, audit_eqns={"reduce_scatter": 2})
    evp.write_text(json.dumps(bad) + "\n")
    r = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "events_summary.py"), str(evp)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "contradict" in r.stderr

    bad2 = dict(good, per_collective=[
        dict(good["per_collective"][0], prim="broadcast")])
    evp.write_text(json.dumps(bad2) + "\n")
    r = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "events_summary.py"), str(evp)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "unknown collective" in r.stderr


# ---------------------------------------------------------------------
# bench digest + forecaster + CLI round-trip

def test_bench_digest_and_comm_fraction():
    from lux_tpu.apps import pagerank

    eng = pagerank.build_engine(mk_graph(), num_parts=2,
                                mesh=mesh_of(2), exchange="owner")
    led = comms.ledger_for(eng)
    d = comms.bench_digest(led, compute_ns=1e6)
    assert d["errors"] == 0
    assert d["ndev"] == 2 and d["exchange"] == "owner"
    assert d["bytes_per_iter"] == led.bytes_per_iter
    assert 0.0 <= d["comm_frac"] <= 1.0
    assert d["comm_bytes_per_edge"] == pytest.approx(
        led.bytes_per_iter * 2 / eng.sg.ne)
    # off-mesh: zero everything
    led1 = comms.ledger_for(
        pagerank.build_engine(mk_graph(), num_parts=2))
    d1 = comms.bench_digest(led1, compute_ns=1e6)
    assert d1["bytes_per_iter"] == 0 and d1["comm_frac"] == 0.0


def test_forecast_table_prices_quantization():
    t = comms.forecast_table(shapes=(("rmat21", 21, 16),),
                             chip_counts=(8,))
    assert "| shape | chips | thinness | quant |" in t
    rows = [ln for ln in t.splitlines() if ln.startswith("| rmat21")]
    assert len(rows) == 4 * 3          # thinness x quant
    # at every thinness, int8 ships fewer ms than bf16 than f32

    def ms(row):
        return float(row.split("|")[5])

    for i in range(0, len(rows), 3):
        f32, bf16, int8 = rows[i], rows[i + 1], rows[i + 2]
        assert ms(int8) < ms(bf16) < ms(f32)
    # quant factors themselves: int8 carries the block-scale overhead
    assert scalemodel.QUANT_FACTORS["int8"] == pytest.approx(0.28125)


def test_cli_roundtrip(tmp_path, capsys):
    evp = tmp_path / "ev.jsonl"
    rc = comms.main(["-configs", "pagerank_np2_gather",
                     "pagerank_mesh2_owner_sum",
                     "cc_mesh2_owner_ring",
                     "-events", str(evp)])
    out = capsys.readouterr().out
    assert rc == 0
    lines = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 3
    by = {d["config"]: d for d in lines}
    assert by["pagerank_np2_gather"]["bytes_per_iter"] == 0
    assert by["pagerank_mesh2_owner_sum"]["bytes_per_iter"] > 0
    ring = by["cc_mesh2_owner_ring"]
    prims = {g["prim"] for g in ring["per_collective"]}
    assert "ppermute" in prims
    # the emitted events render + audit clean
    r = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "events_summary.py"), str(evp)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "comm ledger" in r.stdout


def test_cli_project_smoke(capsys):
    rc = comms.main(["-project"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "thinness" in out and "int8" in out
