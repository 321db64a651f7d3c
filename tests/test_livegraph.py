"""lux_tpu/livegraph.py: live graphs — crash-consistent mutation log,
snapshot-isolated epochs, incremental revalidation, chaos-drilled
compaction (ISSUE 15, round 20), and the FULL mutation algebra
(ISSUE 16, round 21): edge deletions + weight updates (v2 WAL
records, v1 bitwise compat), the anti-monotone re-seed proved equal
to full recompute at the same epoch (bitwise for the integer apps)
against the decremental oracles, pull-kind incremental revalidation
(pagerank epochs advance WITHOUT a fold), and the economics-driven
CompactionScheduler soak-proven on mesh8 under seeded mixed traffic
with bounded occupancy and zero delta_full sheds.

THE chaos acceptance: oversubscribed mixed-kind open-loop loadgen
traffic on the 8-virtual-device mesh with a LIVE mutation stream
(ingest concurrent with the drain), one replica killed mid-drain AND
one injected crash mid-compaction — every admitted answer equals its
NumPy oracle evaluated at the query's ADMISSION epoch (bitwise for
the integer apps), zero torn reads (the events_summary torn-epoch
audit is armed on every live answer), zero duplicate retirements, and
the WAL replay after the crash is bitwise-identical.

Plus: WAL round-trip/torn-tail/typed-corruption units, the
MUT_CRASH / WAL_TORN / COMPACT_CRASH fault legs, incremental oracles
proved equal to full recompute, the device revalidation proved equal
at the same epoch (per-column epochs = snapshot isolation inside one
dispatch), the epoch-keyed answer cache (a stale-epoch hit is a test
failure), and the delta_full backpressure shed.
"""

import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from lux_tpu import faults, format as luxfmt, telemetry
from lux_tpu.apps import components, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph
from lux_tpu.livegraph import (CompactPinnedError, DeltaFullError,
                               EPOCH_SENTINEL, LiveGraph, MutationLog,
                               MutationLogError, check_live_answers)

REPO = Path(__file__).resolve().parent.parent
SUMMARY = REPO / "scripts" / "events_summary.py"
FSCK = REPO / "scripts" / "fsck_lux.py"
sys.path.insert(0, str(REPO / "scripts"))
sys.path.insert(0, str(REPO))

NV, NE, SEED = 256, 2048, 5


@pytest.fixture(scope="module")
def g():
    src, dst = uniform_random_edges(NV, NE, seed=SEED)
    return Graph.from_edges(src, dst, NV)


@pytest.fixture(scope="module")
def gw():
    src, dst = uniform_random_edges(NV, NE, seed=SEED)
    rng = np.random.default_rng(11)
    w = rng.uniform(0.5, 4.0, size=NE).astype(np.float32)
    return Graph.from_edges(src, dst, NV, weights=w)


def _mutations(nv, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(nv, size=n), rng.integers(nv, size=n)


def _sssp_host(eng, label):
    import jax
    h = eng.sg.from_padded(np.asarray(jax.device_get(label)))
    return np.where(h >= int(sssp.HOP_INF), int(sssp.HOP_INF),
                    h.astype(np.int64))


def _clamp_ref(ref):
    return np.where(ref >= int(sssp.HOP_INF), int(sssp.HOP_INF), ref)


def _wal_state(lg: LiveGraph):
    """Everything the WAL-replay bitwise contract covers.  Round-21
    leaves (d_kind, the deletion/reweight counters) append at the
    END — test_wal_torn_fault_mid_append slices positionally."""
    return (lg.base.row_ptrs.copy(), lg.base.col_idx.copy(),
            None if lg.base.weights is None else lg.base.weights.copy(),
            lg.d_src.copy(), lg.d_dst.copy(), lg.d_w.copy(),
            lg.d_epoch.copy(), lg.count, lg.epoch, lg.base_epoch,
            lg.generation, lg.compactions, lg.d_kind.copy(),
            lg.deletions, lg.reweights)


def _live_edge(g, i: int = 0):
    """The i-th base edge — a guaranteed-live deletion/reweight
    target at epoch 0."""
    src, dst = g.edge_arrays()
    return int(src[i]), int(dst[i])


def _phantom_edge(g):
    """A (src, dst) pair that is NOT an edge of g."""
    src, dst = g.edge_arrays()
    have = set(zip(src.tolist(), dst.tolist()))
    for s in range(g.nv):
        for d in range(g.nv):
            if (s, d) not in have:
                return s, d
    raise AssertionError("complete graph")


def _assert_state_equal(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if isinstance(x, np.ndarray):
            np.testing.assert_array_equal(x, y, err_msg=f"leaf {i}")
        else:
            assert x == y, f"leaf {i}: {x} != {y}"


# ---------------------------------------------------------------------
# the mutation log


class TestMutationLog:
    def test_wal_roundtrip_bitwise(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=64, wal_path=wal)
        s1, d1 = _mutations(g.nv, 5, 1)
        s2, d2 = _mutations(g.nv, 3, 2)
        lg.append_edges(s1, d1)
        lg.append_edges(s2, d2)
        want = _wal_state(lg)
        lg.close()
        lg2 = LiveGraph.recover(g, wal)
        _assert_state_equal(_wal_state(lg2), want)
        # the recovered log is RESUMABLE: the chain continues
        lg2.append_edges([1], [2])
        lg2.close()
        lg3 = LiveGraph.recover(g, wal)
        assert lg3.epoch == 3 and lg3.count == 9
        lg3.close()

    def test_torn_tail_at_rest_truncated(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=16, wal_path=wal)
        lg.append_edges([1, 2], [3, 4])
        want = _wal_state(lg)
        lg.close()
        faults.tear_wal(wal, keep_bytes=9)
        recs, _nv, _cap, torn = MutationLog.scan(wal, nv=g.nv)
        assert len(recs) == 2 and torn == 9
        ev = telemetry.EventLog()
        with telemetry.use(events=ev):
            lg2 = LiveGraph.recover(g, wal)
        _assert_state_equal(_wal_state(lg2), want)
        assert any(e["kind"] == "wal_truncate" for e in ev.events)
        # the truncation really happened on disk: a re-scan is clean
        _, _, _, torn2 = MutationLog.scan(wal, nv=g.nv)
        assert torn2 == 0
        lg2.close()

    def test_weights_on_unweighted_live_graph_refused(self, g):
        """REGRESSION: weights passed to an unweighted live graph
        were silently zeroed — journaled as 0.0 bits and served as
        hop counts with no signal the caller's data vanished.
        Graph.with_edges refuses this same mismatch typed."""
        lg = LiveGraph(g, capacity=8)
        with pytest.raises(ValueError, match="UNWEIGHTED"):
            lg.append_edges([1], [2], weights=[2.5])
        assert lg.count == 0 and lg.epoch == 0

    def test_existing_wal_refused_typed(self, g, tmp_path):
        """REGRESSION: restarting with the same construction call
        after a crash — the very situation the WAL exists for — used
        to die on a raw FileExistsError; every other integrity
        refusal here is typed.  The refusal now names recover()."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=8, wal_path=wal)
        lg.append_edges([1], [2])
        lg.close()
        with pytest.raises(MutationLogError, match="recover") as ei:
            LiveGraph(g, capacity=8, wal_path=wal)
        assert ei.value.check == "wal_exists"
        # the durable history is untouched by the refusal
        lg2 = LiveGraph.recover(g, wal)
        assert lg2.count == 1 and lg2.epoch == 1
        lg2.close()

    def test_tear_wal_clamped_to_strict_record_prefix(self, g,
                                                      tmp_path):
        """REGRESSION: a mid-append tear is by definition a STRICT
        record prefix, but tear_wal(keep_bytes >= WAL_RECORD_SIZE)
        used to append a full-record-sized garbage tail — which scan
        rightly classifies as hard crc_chain corruption of a
        possibly-acknowledged record, the opposite of the
        recoverable torn tail the helper promises.  The clamp keeps
        every keep_bytes recoverable."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=16, wal_path=wal)
        lg.append_edges([1, 2], [3, 4])
        want = _wal_state(lg)
        lg.close()
        faults.tear_wal(wal, keep_bytes=luxfmt.WAL_RECORD_SIZE)
        recs, _nv, _cap, torn = MutationLog.scan(wal, nv=g.nv)
        assert len(recs) == 2
        assert 0 < torn < luxfmt.WAL_RECORD_SIZE
        lg2 = LiveGraph.recover(g, wal)
        _assert_state_equal(_wal_state(lg2), want)
        lg2.close()

    def test_wal_torn_fault_mid_append(self, g, tmp_path):
        """The WAL_TORN leg: the injected crash tears the record
        mid-write; replay truncates and recovers the exact
        pre-append state."""
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(
            schedule={3: faults.WAL_TORN})
        lg = LiveGraph(g, capacity=16, wal_path=wal, fault=plan)
        lg.append_edges([1, 2], [3, 4])
        want = _wal_state(lg)
        with pytest.raises(faults.InjectedWorkerCrash):
            lg.append_edges([5, 6], [7, 8])
        assert plan.fired == [(3, faults.WAL_TORN)]
        lg.close()
        lg2 = LiveGraph.recover(g, wal)
        # the durable prefix of the crashed batch replays (edge 5->7
        # landed whole before the tear at the second edge)
        assert lg2.count == 3 and lg2.epoch == 2
        np.testing.assert_array_equal(lg2.d_src[:3], [1, 2, 5])
        # the pre-batch state is a strict prefix: nothing invented
        _assert_state_equal(
            tuple(x[:2] if isinstance(x, np.ndarray) and x.shape
                  and len(x) == 16 else x
                  for x in _wal_state(lg2)[:7]) + _wal_state(lg2)[9:],
            tuple(x[:2] if isinstance(x, np.ndarray) and x.shape
                  and len(x) == 16 else x
                  for x in want[:7]) + want[9:])
        lg2.close()

    def test_mut_crash_leaves_nothing(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(
            schedule={2: faults.MUT_CRASH})
        lg = LiveGraph(g, capacity=16, wal_path=wal, fault=plan)
        lg.append_edges([1, 2], [3, 4])
        want = _wal_state(lg)
        with pytest.raises(faults.InjectedWorkerCrash):
            lg.append_edges([9], [10])
        lg.close()
        lg2 = LiveGraph.recover(g, wal)
        _assert_state_equal(_wal_state(lg2), want)
        lg2.close()

    def test_midfile_corruption_typed(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=16, wal_path=wal)
        lg.append_edges([1, 2, 3], [4, 5, 6])
        lg.close()
        blob = bytearray(open(wal, "rb").read())
        blob[luxfmt.WAL_HEADER_SIZE + 4] ^= 0xFF
        open(wal, "wb").write(bytes(blob))
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "crc_chain"
        with pytest.raises(MutationLogError):
            LiveGraph.recover(g, wal)

    def test_full_record_bad_crc_tail_is_corruption(self, g,
                                                    tmp_path):
        """A FULL-SIZE final record failing its CRC is rot of a
        possibly-fsync-acknowledged append — a torn append can only
        leave a strict prefix — so scan must raise crc_chain, never
        silently truncate an acknowledged mutation away."""
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 8)
        log.append_edge(1, 1, 2, 0)
        log.append_edge(2, 3, 4, 0)
        log.close()
        blob = bytearray(open(wal, "rb").read())
        blob[-10] ^= 0xFF               # inside the LAST record
        open(wal, "wb").write(bytes(blob))
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "crc_chain"
        assert "acknowledged" in str(ei.value)

    def test_epoch_regression_typed(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 16)
        log.append_edge(3, 1, 2, 0)
        log.append_edge(1, 3, 4, 0)     # epoch going BACKWARDS
        log.close()
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "epoch_order"

    def test_unknown_record_kind_typed(self, g, tmp_path):
        from lux_tpu.livegraph import _pack_record
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 16)
        log._append(_pack_record(1, 9, 0, 0, 0, log._crc))
        log.close()
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "record_kind"

    def test_foreign_graph_header_typed(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=16, wal_path=wal)
        lg.append_edges([1], [2])
        lg.close()
        with pytest.raises(luxfmt.GraphFormatError) as ei:
            MutationLog.scan(wal, nv=g.nv + 1)
        assert ei.value.check == "wal_header"
        # garbage file: header magic check
        bad = str(tmp_path / "junk.wal")
        open(bad, "wb").write(b"NOPE" + b"\0" * 20)
        with pytest.raises(luxfmt.GraphFormatError) as ei:
            MutationLog.scan(bad)
        assert ei.value.check == "wal_header"

    def test_compact_done_without_start_typed(self, g, tmp_path):
        from lux_tpu.livegraph import REC_COMPACT_DONE
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 16)
        log.append_edge(1, 1, 2, 0)
        log.append_marker(1, REC_COMPACT_DONE, 1, 1)
        log.close()
        with pytest.raises(MutationLogError) as ei:
            LiveGraph.recover(g, wal)
        assert ei.value.check == "compact_pair"

    def test_capacity_overflow_replay_typed(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 2)
        for i in range(3):
            log.append_edge(i + 1, 1, 2, 0)
        log.close()
        with pytest.raises(MutationLogError) as ei:
            LiveGraph.recover(g, wal)
        assert ei.value.check == "capacity_overflow"

    def test_fsck_wal_legs(self, g, tmp_path):
        """scripts/fsck_lux.py knows the WAL format: clean log OK,
        torn tail reported-but-clean, corruption exit 2, a sidecar
        from a different graph exit 2."""
        lux = str(tmp_path / "g.lux")
        luxfmt.write_lux(lux, g.row_ptrs, g.col_idx)
        wal = luxfmt.wal_sidecar_path(lux)
        lg = LiveGraph(g, capacity=16, wal_path=wal)
        lg.append_edges([1, 2], [3, 4])
        lg.compact(force=True)
        lg.close()
        r = subprocess.run([sys.executable, str(FSCK), lux],
                           capture_output=True, text=True)
        assert r.returncode == 0 and "OK wal" in r.stdout
        faults.tear_wal(wal)
        r = subprocess.run([sys.executable, str(FSCK), wal],
                           capture_output=True, text=True)
        assert r.returncode == 0 and "TORN-TAIL" in r.stdout
        blob = bytearray(open(wal, "rb").read())
        blob[luxfmt.WAL_HEADER_SIZE + 1] ^= 0xFF
        open(wal, "wb").write(bytes(blob))
        r = subprocess.run([sys.executable, str(FSCK), wal],
                           capture_output=True, text=True)
        assert r.returncode == 2 and "crc_chain" in r.stderr


# ---------------------------------------------------------------------
# the live graph: epochs, delta blocks, compaction


class TestLiveGraph:
    def test_epochs_monotone_and_delta_full(self, g):
        lg = LiveGraph(g, capacity=4)
        assert lg.append_edges([1], [2]) == 1
        assert lg.append_edges([3, 4], [5, 6]) == 2
        assert lg.epoch == 2 and lg.count == 3
        assert lg.occupancy() == 0.75
        with pytest.raises(DeltaFullError):
            lg.append_edges([7, 8], [9, 10])
        # the refused batch published NOTHING (epoch and slots)
        assert lg.epoch == 2 and lg.count == 3
        # unwritten slots carry the sentinel (torn-read-free mask)
        assert lg.d_epoch[3] == EPOCH_SENTINEL

    def test_append_validation_typed(self, g, gw):
        lg = LiveGraph(g, capacity=4)
        with pytest.raises(ValueError, match="length mismatch"):
            lg.append_edges([1, 2], [3])
        with pytest.raises(ValueError, match="outside"):
            lg.append_edges([g.nv], [0])
        with pytest.raises(ValueError, match="weights"):
            LiveGraph(gw, capacity=4).append_edges([1], [2])
        # a SHORT weights array must refuse BEFORE any WAL append /
        # delta publish — not IndexError mid-batch with edges already
        # durable
        lw = LiveGraph(gw, capacity=4)
        with pytest.raises(ValueError, match="weights length"):
            lw.append_edges([1, 2, 3], [4, 5, 6], weights=[0.5, 0.5])
        assert lw.epoch == 0 and lw.count == 0
        with pytest.raises(ValueError, match="capacity"):
            LiveGraph(g, capacity=0)

    def test_graph_at_is_the_oracle_surface(self, g):
        lg = LiveGraph(g, capacity=8)
        s1, d1 = _mutations(g.nv, 3, 3)
        lg.append_edges(s1, d1)
        assert lg.graph_at(0).ne == g.ne
        g1 = lg.graph_at(1)
        want = g.with_edges(s1, d1)
        np.testing.assert_array_equal(g1.row_ptrs, want.row_ptrs)
        np.testing.assert_array_equal(g1.col_idx, want.col_idx)
        with pytest.raises(ValueError):
            lg.graph_at(2)

    def test_compact_swaps_generation_atomically(self, g, tmp_path):
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=8, wal_path=wal,
                       compact_threshold=0.5)
        s1, d1 = _mutations(g.nv, 4, 4)
        lg.append_edges(s1, d1)
        assert lg.should_compact()
        eco = lg.compact_economics()
        assert eco["should_compact"] and eco["delta_count"] == 4
        old_delta = lg.d_epoch      # published block stays immutable
        assert lg.compact() == 1
        assert lg.generation == 1 and lg.base_epoch == 1
        assert lg.count == 0 and lg.base.ne == g.ne + 4
        # FRESH arrays, not zeroed-under-the-reader ones
        assert lg.d_epoch is not old_delta
        assert (old_delta[:4] == 1).all()
        # pull view now sees the folded epoch; push always the latest
        assert lg.view_epoch("pull") == 1
        assert lg.view_epoch("push") == 1
        lg.close()
        # recovery re-folds the COMPLETED compaction bitwise
        lg2 = LiveGraph.recover(g, wal)
        np.testing.assert_array_equal(lg2.base.row_ptrs,
                                      lg.base.row_ptrs)
        np.testing.assert_array_equal(lg2.base.col_idx,
                                      lg.base.col_idx)
        assert lg2.generation == 1 and lg2.count == 0
        lg2.close()

    def test_compact_refused_while_pinned(self, g):
        lg = LiveGraph(g, capacity=4)
        lg.append_edges([1], [2])
        lg.pin()
        with pytest.raises(CompactPinnedError):
            lg.compact(force=True)
        lg.unpin()
        assert lg.compact(force=True) == 1

    def test_compact_crash_recovers_surviving_generation(
            self, g, tmp_path):
        """THE COMPACT_CRASH leg: the crash lands between the WAL
        COMPACT_START marker and the generation swap; recovery comes
        up on the SURVIVING generation (origin base + full published
        delta) bitwise, and the next compaction completes."""
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(
            compact_schedule={0: faults.COMPACT_CRASH})
        lg = LiveGraph(g, capacity=8, wal_path=wal, fault=plan)
        s1, d1 = _mutations(g.nv, 5, 6)
        lg.append_edges(s1, d1)
        want = _wal_state(lg)
        with pytest.raises(faults.InjectedWorkerCrash):
            lg.compact(force=True)
        assert plan.fired == [(0, faults.COMPACT_CRASH)]
        lg.close()
        # the log holds a START without a DONE; fsck still reports
        # the file clean (an open compaction is a crash signature,
        # not corruption)
        r = subprocess.run([sys.executable, str(FSCK), wal],
                           capture_output=True, text=True)
        assert r.returncode == 0 and "open-compaction" in r.stdout
        lg2 = LiveGraph.recover(g, wal)
        _assert_state_equal(_wal_state(lg2), want)
        # and the generation is fully usable: compact completes now
        assert lg2.compact(force=True) == 1
        assert lg2.base.ne == g.ne + 5
        lg2.close()

    def test_concurrent_append_during_compact_loses_nothing(
            self, g, tmp_path):
        """compact() holds the mutation lock end to end: an append
        racing the ~40ms fold must land either wholly BEFORE the
        swap (folded into the new base) or wholly AFTER (published
        in the fresh delta) — never silently dropped, and never as
        an epoch-e+1 WAL record ahead of the epoch-e START marker
        (which would fail the log's own epoch_order validation)."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(g, capacity=512, wal_path=wal)
        stop = threading.Event()
        drained = threading.Event()
        appended = []

        def ingest():
            rng = np.random.default_rng(99)
            while not stop.is_set():
                s = int(rng.integers(g.nv))
                d = int(rng.integers(g.nv))
                try:
                    lg.append_edges([s], [d])
                except DeltaFullError:
                    # wait for the compactor's signal instead of a
                    # wall-clock sleep (flaky under CI load); the
                    # timeout is liveness only, not pacing
                    drained.clear()
                    drained.wait(0.1)
                    continue
                appended.append((s, d))

        th = threading.Thread(target=ingest)
        th.start()
        compactions = 0
        deadline = time.monotonic() + 3.0
        while compactions < 4 and time.monotonic() < deadline:
            if lg.compact(force=True) is not None:
                compactions += 1
                drained.set()
        stop.set()
        drained.set()
        th.join()
        assert compactions >= 2 and len(appended) > 0
        # every acknowledged edge is in new-base-or-delta
        total = lg.base.ne + lg.count
        assert total == g.ne + len(appended)
        lg.close()
        # and the WAL both scans clean and replays to the same count
        lg2 = LiveGraph.recover(g, wal)
        assert lg2.base.ne + lg2.count == g.ne + len(appended)
        lg2.close()


# ---------------------------------------------------------------------
# incremental oracles — proved equal to full recompute


class TestIncrementalOracles:
    @pytest.mark.parametrize("n_new,seed", [(1, 21), (7, 22),
                                            (40, 23)])
    def test_sssp_incremental_equals_full(self, g, n_new, seed):
        src, dst = _mutations(g.nv, n_new, seed)
        g_new = g.with_edges(src, dst)
        d0 = sssp.reference_sssp(g, 0)
        inc = sssp.reference_sssp_incremental(g_new, d0, src, dst)
        np.testing.assert_array_equal(inc,
                                      sssp.reference_sssp(g_new, 0))

    @pytest.mark.parametrize("n_new,seed", [(3, 31), (25, 32)])
    def test_sssp_weighted_incremental_equals_full(self, gw, n_new,
                                                   seed):
        src, dst = _mutations(gw.nv, n_new, seed)
        rng = np.random.default_rng(seed)
        w = rng.uniform(0.5, 4.0, size=n_new).astype(np.float32)
        g_new = gw.with_edges(src, dst, w)
        d0 = sssp.reference_sssp(gw, 0, weighted=True)
        inc = sssp.reference_sssp_incremental(
            g_new, d0, src, dst, new_w=w, weighted=True)
        np.testing.assert_array_equal(
            inc, sssp.reference_sssp(g_new, 0, weighted=True))

    def test_weighted_incremental_requires_new_w(self, gw):
        """A silently one-weighted append would seed BELOW the true
        fixed point — unrepairable by monotone propagation, so the
        oracle refuses (the Graph.with_edges contract)."""
        g_new = gw.with_edges([1], [2], [2.0])
        d0 = sssp.reference_sssp(gw, 0, weighted=True)
        with pytest.raises(ValueError, match="new_w"):
            sssp.reference_sssp_incremental(g_new, d0, [1], [2],
                                            weighted=True)

    @pytest.mark.parametrize("n_new,seed", [(1, 41), (7, 42),
                                            (40, 43)])
    def test_components_incremental_equals_full(self, g, n_new, seed):
        src, dst = _mutations(g.nv, n_new, seed)
        g_new = g.with_edges(src, dst)
        c0 = components.reference_components(g)
        inc = components.reference_components_incremental(
            g_new, c0, src, dst)
        np.testing.assert_array_equal(
            inc, components.reference_components(g_new))


# ---------------------------------------------------------------------
# device revalidation — proved equal at the same epoch


class TestRevalidate:
    @pytest.mark.parametrize("num_parts", [1, 2])
    def test_sssp_revalidate_bitwise(self, g, num_parts):
        eng = sssp.build_engine(g, 0, num_parts=num_parts)
        lab, act = eng.init_state()
        lab, act, _ = eng.converge(lab, act)
        lg = LiveGraph(g, capacity=32)
        s1, d1 = _mutations(g.nv, 9, 51)
        lg.append_edges(s1, d1)
        lab, act, _ = lg.revalidate(eng, lab, act)
        ref = _clamp_ref(sssp.reference_sssp(lg.graph_at(1), 0))
        np.testing.assert_array_equal(_sssp_host(eng, lab), ref)

    def test_sssp_weighted_revalidate(self, gw):
        import jax
        eng = sssp.build_engine(gw, 0, num_parts=2, weighted=True)
        lab, act = eng.init_state()
        lab, act, _ = eng.converge(lab, act)
        lg = LiveGraph(gw, capacity=32)
        s1, d1 = _mutations(gw.nv, 9, 52)
        rng = np.random.default_rng(52)
        w = rng.uniform(0.5, 4.0, size=9).astype(np.float32)
        lg.append_edges(s1, d1, w)
        lab, act, _ = lg.revalidate(eng, lab, act)
        h = eng.sg.from_padded(np.asarray(jax.device_get(lab)))
        ref = sssp.reference_sssp(lg.graph_at(1), 0, weighted=True)
        reach = np.isfinite(ref)
        np.testing.assert_allclose(h[reach], ref[reach], rtol=1e-5)
        assert not np.isfinite(h[~reach]).any()

    def test_components_revalidate_bitwise(self, g):
        import jax
        eng = components.build_engine(g, num_parts=2)
        lab, act = eng.init_state()
        lab, act, _ = eng.converge(lab, act)
        lg = LiveGraph(g, capacity=32)
        s1, d1 = _mutations(g.nv, 9, 53)
        lg.append_edges(s1, d1)
        lab, act, _ = lg.revalidate(eng, lab, act)
        h = eng.sg.from_padded(np.asarray(jax.device_get(lab)))
        ref = components.reference_components(lg.graph_at(1))
        np.testing.assert_array_equal(h.astype(np.int64), ref)

    def test_batched_per_column_epochs_snapshot_isolated(self, g):
        """Snapshot isolation INSIDE one dispatch: four query columns
        pinned to epochs [0, 1, 2, 2] share one delta-relax + one
        converge, and each lands bitwise on the oracle of ITS OWN
        epoch's graph — the per-column epoch mask is the machine
        proof that a column can never see a later edge."""
        sources = [3, 17, 40, 99]
        eng = sssp.build_engine(g, num_parts=2, sources=sources)
        lab, act = eng.init_state()
        lab, act, _ = eng.converge(lab, act)
        lg = LiveGraph(g, capacity=32)
        s1, d1 = _mutations(g.nv, 8, 54)
        s2, d2 = _mutations(g.nv, 8, 55)
        lg.append_edges(s1, d1)     # epoch 1
        lg.append_edges(s2, d2)     # epoch 2
        col_epoch = np.array([0, 1, 2, 2], np.int32)
        lab, act, _ = lg.revalidate(eng, lab, act,
                                    col_epoch=col_epoch)
        h = _sssp_host(eng, lab)    # [nv, B]
        for q, (s, e) in enumerate(zip(sources, col_epoch)):
            ref = _clamp_ref(sssp.reference_sssp(lg.graph_at(int(e)),
                                                 s))
            np.testing.assert_array_equal(
                h[:, q], ref,
                err_msg=f"column {q} pinned to epoch {e}")

    def test_revalidate_mesh8(self, g):
        from lux_tpu.parallel.mesh import make_mesh
        eng = sssp.build_engine(g, 0, num_parts=8, mesh=make_mesh(8))
        lab, act = eng.init_state()
        lab, act, _ = eng.converge(lab, act)
        lg = LiveGraph(g, capacity=32)
        s1, d1 = _mutations(g.nv, 9, 56)
        lg.append_edges(s1, d1)
        lab, act, _ = lg.revalidate(eng, lab, act)
        ref = _clamp_ref(sssp.reference_sssp(lg.graph_at(1), 0))
        np.testing.assert_array_equal(_sssp_host(eng, lab), ref)

    def test_delta_step_rejects_pull_programs(self, g):
        from lux_tpu.apps import pagerank
        eng = pagerank.build_engine(g, num_parts=2)
        lg = LiveGraph(g, capacity=8)
        with pytest.raises(ValueError, match="monotone"):
            lg.delta_step(eng)

    def test_dead_cache_entries_evicted(self, g):
        """REGRESSION: the id()-keyed geometry/engine caches validate
        hits by weakref identity but never dropped dead entries —
        every refresh_live rebuilds engines at fresh addresses, so a
        long-lived server leaked an O(nv) slot map and a compiled
        step per retired generation.  A miss now sweeps dead
        referents, bounding each cache at the live engines."""
        import gc
        lg = LiveGraph(g, capacity=16)
        lg.append_edges([1, 2], [3, 4])
        for _ in range(4):
            eng = sssp.build_engine(g, 0, num_parts=2)
            lab, act = eng.init_state()
            lab, act, _ = eng.converge(lab, act)
            lg.revalidate(eng, lab, act)
            del eng, lab, act
            gc.collect()
        assert len(lg._vslot_cache) <= 1
        assert len(lg._slot_cache) <= 1
        assert len(lg._step_cache) <= 1

    def test_delta_step_audits_clean(self, g):
        """The delta-relax step holds the engines' own gather budget
        (ONE state-table gather) under the repo auditor — the same
        machine check the three ``*_live_*`` matrix configs run
        repo-wide in tests/test_audit.py."""
        from lux_tpu import audit
        eng = sssp.build_engine(g, 0, num_parts=2)
        lg = LiveGraph(g, capacity=16)
        lg.append_edges([1, 2], [3, 4])
        lg.register_audit(eng)
        assert audit.audit_engine(eng, mode=None) == []


# ---------------------------------------------------------------------
# serving: epoch pinning, the answer cache, backpressure


class TestServeLive:
    def _server(self, g, lg, **kw):
        from lux_tpu import serve
        kw.setdefault("batch", 2)
        kw.setdefault("num_parts", 2)
        kw.setdefault("seg_iters", 4)
        return serve.Server(g, live=lg, **kw)

    def test_mixed_epochs_in_one_drain(self, g):
        """Queries admitted at DIFFERENT epochs share one drain (one
        batched dispatch) and each answers bitwise at its own
        admission epoch — the serving-layer snapshot-isolation
        proof."""
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg, batch=4)
        srv.submit("sssp", source=3)
        srv.submit("components", source=17)
        s1, d1 = _mutations(g.nv, 10, 61)
        srv.mutate(s1, d1)
        srv.submit("sssp", source=3)        # same source, NEW epoch
        srv.submit("components", source=17)
        responses = srv.run()
        assert len(responses) == 4
        epochs = sorted(r.epoch for r in responses)
        assert epochs == [0, 0, 1, 1]
        assert check_live_answers(lg, responses) == 0
        # the two sssp answers genuinely differ across the epochs or
        # the isolation claim is vacuous for this seed
        a = {(r.kind, r.epoch): r.answer for r in responses}
        assert not np.array_equal(a[("sssp", 0)], a[("sssp", 1)]) \
            or not np.array_equal(a[("components", 0)],
                                  a[("components", 1)])

    def test_cache_hits_same_epoch_invalidated_on_advance(self, g):
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg, cache=True)
        srv.submit("sssp", source=7)
        r1 = srv.run()
        srv.submit("sssp", source=7)        # same epoch: HIT
        r2 = srv.run()
        assert [r.cached for r in r2] == [True]
        assert r2[0].segments == 0
        np.testing.assert_array_equal(r1[0].answer, r2[0].answer)
        assert srv.cache.hits == 1
        s1, d1 = _mutations(g.nv, 5, 62)
        srv.mutate(s1, d1)
        srv.submit("sssp", source=7)        # new epoch: MISS
        r3 = srv.run()
        assert not r3[0].cached and r3[0].epoch == 1
        assert check_live_answers(lg, r1 + r2 + r3) == 0
        # the epoch-0 entries were swept on the advance (no view
        # exposes epoch 0 anymore)
        assert all(k[2] != 0 for k in srv.cache._d)

    def test_cache_byte_budget_binds_on_big_answers(self):
        """REGRESSION: an entry-count cap alone scales cache memory
        with GRAPH SIZE (each entry copies a full nv-length answer) —
        the byte budget must evict LRU before the count cap on big
        answers, and the ledger must stay exact across replace/
        expire/sweep."""
        from lux_tpu.serve import AnswerCache, Request
        cache = AnswerCache(max_entries=64, max_bytes=4096)
        ans = np.zeros(256, np.int32)           # 1024 B each
        for s in range(8):
            cache.put("sssp", Request(qid=s, kind="sssp", source=s,
                                      t_enqueue=0.0, epoch=0),
                      ans, 1, 0, 0.0)
        assert len(cache._d) == 4               # 4 x 1024 = budget
        assert cache.bytes == 4096
        # LRU: the oldest sources were evicted, the newest retained
        hit = cache.get("sssp", Request(qid=9, kind="sssp", source=7,
                                        t_enqueue=0.0, epoch=0), 0.0)
        assert hit is not None
        miss = cache.get("sssp", Request(qid=10, kind="sssp",
                                         source=0, t_enqueue=0.0,
                                         epoch=0), 0.0)
        assert miss is None
        # replacing a key must not double-count its bytes
        cache.put("sssp", Request(qid=11, kind="sssp", source=7,
                                  t_enqueue=0.0, epoch=0),
                  ans, 1, 0, 0.0)
        assert cache.bytes == 4096 and len(cache._d) == 4
        # true LRU, not FIFO: a hit renews recency, so the hot
        # oldest-inserted entry survives the next eviction and the
        # cold one goes instead
        assert cache.get("sssp", Request(qid=12, kind="sssp",
                                         source=4, t_enqueue=0.0,
                                         epoch=0), 0.0) is not None
        cache.put("sssp", Request(qid=13, kind="sssp", source=8,
                                  t_enqueue=0.0, epoch=0),
                  ans, 1, 0, 0.0)
        assert cache.get("sssp", Request(qid=14, kind="sssp",
                                         source=4, t_enqueue=0.0,
                                         epoch=0), 0.0) is not None
        assert cache.get("sssp", Request(qid=15, kind="sssp",
                                         source=5, t_enqueue=0.0,
                                         epoch=0), 0.0) is None
        # sweep keeps the ledger exact
        cache.sweep({"sssp": 1})
        assert len(cache._d) == 0 and cache.bytes == 0

    def test_stale_epoch_hit_is_a_test_failure(self, g):
        """A stale-epoch hit is impossible BY KEY; this pins the
        oracle harness that would catch the bug class anyway: poison
        the cache with an old-epoch answer under the new epoch's key
        and the per-epoch oracle check MUST flag the served
        response."""
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg, cache=True)
        srv.submit("sssp", source=3)
        (r0,) = srv.run()
        # mutate so the epoch-1 answer for source 3 changes
        rng = np.random.default_rng(63)
        while True:
            s1, d1 = rng.integers(g.nv, size=6), rng.integers(
                g.nv, size=6)
            if not np.array_equal(
                    _clamp_ref(sssp.reference_sssp(
                        g.with_edges(s1, d1), 3)),
                    _clamp_ref(sssp.reference_sssp(g, 3))):
                break
        srv.mutate(s1, d1)
        # POISON: the epoch-0 answer filed under the epoch-1 key —
        # exactly what a buggy cache would serve
        from lux_tpu.serve import Request
        fake = Request(qid=-1, kind="sssp", source=3, t_enqueue=0.0,
                       epoch=1)
        srv.cache.put("sssp", fake, r0.answer, r0.iters, 1, 0.0)
        srv.submit("sssp", source=3)
        (r1,) = srv.run()
        assert r1.cached    # the poisoned entry served
        assert check_live_answers(lg, [r1]) == 1, \
            "the oracle harness failed to flag a stale-epoch answer"

    def test_pagerank_advances_epochs_without_fold(self, g):
        """Round 21 (pull-kind incremental revalidation): appends
        advance the PULL admission epoch with NO compaction — the
        engine normalizes by effective degree (the deg_corr program
        array) and the drain hook adds the delta appends' rank mass
        per column's admission epoch, together one exact PPR
        iteration over graph_at(epoch)."""
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg)
        s1, d1 = _mutations(g.nv, 6, 64)
        srv.mutate(s1, d1)
        srv.submit("pagerank", source=5)
        (r,) = srv.run()
        assert r.epoch == 1 and lg.compactions == 0
        assert check_live_answers(lg, [r]) == 0
        # a DELETION caps pull admission below its epoch — the host
        # correction is append-linear and cannot express an
        # anti-monotone op
        ds, dd = _live_edge(g, 3)
        srv.mutate([ds], [dd], op="delete")
        srv.submit("pagerank", source=5)
        (r2,) = srv.run()
        assert r2.epoch == 1
        assert check_live_answers(lg, [r2]) == 0
        # the fold + adoption advances past the deletion
        lg.compact(force=True)
        srv.refresh_live()
        srv.submit("pagerank", source=5)
        (r3,) = srv.run()
        assert r3.epoch == 2
        assert check_live_answers(lg, [r3]) == 0

    def test_two_pagerank_epochs_in_one_batch_corrected_on_device(
            self, g):
        """PR 27: the pull correction is a device program.  Two
        pagerank columns admitted at two epochs share one batch and
        both answer at their own epoch; column for column the device
        degree correction IS the host's (counts: exact), and the
        device rank-mass step is the host correction it replaced on
        the same ``prev`` within float32 summation order: the host
        added a destination's delta edges in slot order
        (``np.add.at``), the device scatter adds them in any order;
        at most four non-negative terms meet in one destination
        here, so the sums agree within 3 ulp (4e-7): ``rtol`` 1e-6."""
        from lux_tpu.apps import pagerank
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg)
        srv.submit("pagerank", source=5)
        srv.mutate([5, 5, 7, 9, 11, 5, 9], [20, 21, 20, 20, 22, 20, 5])
        srv.submit("pagerank", source=5)
        responses = srv.run()
        assert sorted(r.epoch for r in responses) == [0, 1]
        assert check_live_answers(lg, responses) == 0
        a, b = (r.answer for r in responses)
        assert not np.array_equal(a, b)

        runner = srv._runner("pagerank")
        eng, sg = runner.eng, runner.eng.sg
        col_epoch = np.array([0, 1], np.int32)
        rng = np.random.default_rng(27)
        prev, new = (rng.random((g.nv, 2)).astype(np.float32)
                     for _ in range(2))
        # the host correction before PR 27
        ds, dd, _dw, de = lg.append_deltas()
        deg_corr = np.zeros((g.nv, 2), np.float32)
        for col, e in enumerate(col_epoch):
            np.add.at(deg_corr[:, col], ds[de <= e], 1.0)
        acc = np.zeros_like(new)
        np.add.at(acc, dd, prev[ds] * (de[:, None] <= col_epoch[None]))
        deg_eff = np.asarray(g.out_degrees,
                             np.float32)[:, None] + deg_corr
        want = (new + pagerank.ALPHA * acc
                / np.maximum(deg_eff, 1.0)).astype(np.float32)
        assert np.count_nonzero(want != new) >= 4
        # the device programs
        delta = lg.delta_arrays(sg)
        table = runner._degrees(
            sg.to_padded(np.full((g.nv, 2), 7, np.float32)),
            np.ones(2, bool), col_epoch, *delta)
        np.testing.assert_array_equal(
            sg.from_padded(np.asarray(table)), deg_corr)
        got = runner._mass(sg.to_padded(new), sg.to_padded(prev),
                           eng.arrays["deg"], table, col_epoch, *delta)
        np.testing.assert_allclose(sg.from_padded(np.asarray(got)),
                                   want, rtol=1e-6, atol=0)

    def test_refresh_live_guards_and_delta_full(self, g):
        lg = LiveGraph(g, capacity=4)
        srv = self._server(g, lg)
        srv.submit("sssp", source=1)
        lg.append_edges([1], [2])
        # the queued query pinned epoch 0 >= base_epoch 0: the delta
        # mask replays it, so adoption must NOT refuse (the old
        # latest-epoch comparison wrongly raised here)
        srv.refresh_live()
        # the defensive arm: an epoch below base_epoch really is
        # irreproducible (an invariant breach — live compaction is
        # ledger-guarded against folding under an admitted query)
        req = srv._collector("sssp").pending_requests()[0]
        req.epoch = -1
        with pytest.raises(RuntimeError, match="reproduce"):
            srv.refresh_live()
        req.epoch = 0
        srv.run()
        with pytest.raises(DeltaFullError):
            srv.mutate(*_mutations(g.nv, 5, 65))
        lg.compact(force=True)
        srv.refresh_live()
        assert srv.g is lg.base
        srv.submit("sssp", source=1)
        (r,) = srv.run()
        assert check_live_answers(lg, [r]) == 0

    def test_run_refuses_stale_generation_then_unwedges(self, g):
        """Generation adoption is ENFORCED: serving on a stale base
        after a compaction would converge old-base + empty delta — a
        wrong answer whose answer_epoch equals its admission epoch.
        run() refuses typed; a query submitted between compact and
        refresh_live re-stamps to the same epoch on the new
        generation, so adoption unwedges it."""
        lg = LiveGraph(g, capacity=8)
        srv = self._server(g, lg)
        lg.append_edges([1], [2])
        srv.submit("sssp", source=1)
        srv.run()
        lg.compact(force=True)
        srv.submit("sssp", source=2)
        with pytest.raises(RuntimeError, match="refresh_live"):
            srv.run()
        srv.refresh_live()
        (r,) = srv.run()
        assert r.epoch == 1
        assert check_live_answers(lg, [r]) == 0

    def test_ingest_between_compact_and_refresh_not_wedged(self, g):
        """REGRESSION: a mutation landing between compact() and
        refresh_live() while a reproducible push query sat queued
        wedged the server three ways — refresh_live refused on a
        false epoch mismatch (it compared against the LATEST view
        epoch, not reproducibility), run() refused on the stale
        base, and compact() refused on the admission ledger, with no
        recovery path.  The query pinned the NEW base_epoch, which
        the per-column delta mask replays exactly; adoption must
        proceed and serve it oracle-correct at its admission
        epoch."""
        lg = LiveGraph(g, capacity=32)
        srv = self._server(g, lg)
        lg.append_edges([1], [2])
        lg.compact(force=True)              # base_epoch -> 1
        srv.submit("sssp", source=3)        # admitted at epoch 1
        s1, d1 = _mutations(g.nv, 6, 91)
        srv.mutate(s1, d1)                  # epoch -> 2
        srv.refresh_live()                  # must NOT raise
        (r,) = srv.run()
        assert r.epoch == 1
        assert check_live_answers(lg, [r]) == 0
        # a query admitted after the ingest serves at the new epoch
        srv.submit("sssp", source=3)
        (r2,) = srv.run()
        assert r2.epoch == 2
        assert check_live_answers(lg, [r2]) == 0

    def test_compact_refuses_admitted_queued_queries(self, g):
        """An admitted-but-QUEUED query already pinned its epoch at
        submit; compacting before it reaches a column would fold the
        delta out from under the old-base engines it will be served
        on — a wrong answer with answer_epoch == admission epoch,
        structurally invisible to the torn-epoch audit.  The
        admission ledger makes compact refuse typed instead."""
        lg = LiveGraph(g, capacity=8)
        srv = self._server(g, lg)
        lg.append_edges([1], [2])
        srv.submit("sssp", source=1)
        with pytest.raises(CompactPinnedError, match="admitted"):
            lg.compact(force=True)
        (r,) = srv.run()
        assert check_live_answers(lg, [r]) == 0
        # drained: the release at retirement re-arms compaction
        assert lg.compact(force=True) == 1

    def test_server_requires_live_base(self, g):
        lg = LiveGraph(g, capacity=4)
        other = g.with_edges([1], [2])
        with pytest.raises(ValueError, match="live.base"):
            self._server(other, lg)

    def test_drag_samples_feed_scheduler_economics(self, g):
        """The serve runners fence-time every Nth delta boundary and
        feed it to the live graph (round 21) — after a few live
        drains the scheduler's economics run on MEASURED drag, not
        the scalemodel term."""
        lg = LiveGraph(g, capacity=64)
        srv = self._server(g, lg, batch=4)
        s1, d1 = _mutations(g.nv, 10, 71)
        srv.mutate(s1, d1)
        for q in range(4):
            srv.submit("sssp", source=q + 1)
        responses = srv.run()
        assert check_live_answers(lg, responses) == 0
        assert len(lg._drag_samples) >= 1
        eco = lg.compact_economics()
        assert eco["drag_source"] == "measured"
        assert eco["drag_samples"] >= 1
        assert eco["delta_drag_ns_per_boundary"] > 0

    def test_mutate_routes_the_algebra(self, g, gw):
        """Server.mutate is the single ingest door for all three
        ops; an unknown op refuses typed."""
        lg = LiveGraph(g, capacity=16)
        srv = self._server(g, lg)
        es, ed = _live_edge(g, 2)
        srv.mutate([es], [ed], op="delete")
        assert lg.deletions == 1 and lg.epoch == 1
        with pytest.raises(ValueError, match="unknown mutation op"):
            srv.mutate([1], [2], op="merge")
        lgw = LiveGraph(gw, capacity=16)
        srvw = self._server(gw, lgw, weighted=True)
        rs, rd = _live_edge(gw, 4)
        srvw.mutate([rs], [rd], weights=[1.25], op="reweight")
        assert lgw.reweights == 1
        # the admission cap is live through the serving door too
        assert lgw.view_epoch("push") == 0
        srvw.submit("sssp", source=3)
        (r,) = srvw.run()
        assert r.epoch == 0
        assert check_live_answers(lgw, [r], weighted=True) == 0


class TestFleetLive:
    def _fleet(self, g, lg, tmp_path, **kw):
        from lux_tpu import fleet, resilience
        kw.setdefault("replicas", 2)
        kw.setdefault("batch", 2)
        kw.setdefault("num_parts", 2)
        kw.setdefault("retry",
                      resilience.RetryPolicy(retries=3,
                                             backoff_s=0.01,
                                             max_backoff_s=0.05,
                                             jitter_seed=0))
        kw.setdefault("board_path", str(tmp_path / "board"))
        return fleet.FleetServer(g, live=lg, **kw)

    def test_failover_answers_at_original_admission_epoch(
            self, g, tmp_path):
        """THE fleet-failover satellite: queries admitted at epoch e,
        the serving replica killed mid-drain, MORE mutations land
        after admission — the re-dispatched queries still answer at
        epoch e, bitwise (integer apps), never at the later epoch."""
        from lux_tpu import fleet
        lg = LiveGraph(g, capacity=64)
        flt = self._fleet(g, lg, tmp_path)
        flt.warm(["sssp", "components"])
        s1, d1 = _mutations(g.nv, 10, 71)
        flt.mutate(s1, d1)                  # epoch 1
        specs = [("sssp", s) for s in (3, 17, 40)] \
            + [("components", s) for s in (7, 50, 120)]
        qids = {}
        for kind, s in specs:
            qids[flt.submit(kind, source=s)] = (kind, s)
        # mutations land AFTER admission: epoch moves to 2, but the
        # in-flight queries stay pinned to 1
        s2, d2 = _mutations(g.nv, 10, 72)
        flt.mutate(s2, d2)
        flt.set_fault(faults.ReplicaKillPlan({"r1": 1}))
        rs = flt.run()
        assert len(rs) == len(specs) and flt.failovers >= 1
        assert all(r.epoch == 1 for r in rs)
        assert check_live_answers(lg, rs) == 0
        # bitwise vs a fault-free fleet serving the SAME epoch
        lg2 = LiveGraph(g, capacity=64)
        lg2.append_edges(s1, d1)
        flt2 = self._fleet(g, lg2, tmp_path)
        want = {}
        for kind, s in specs:
            want[flt2.submit(kind, source=s)] = (kind, s)
        rs2 = flt2.run()
        by_spec = {qids[r.qid]: r.answer for r in rs}
        by_spec2 = {want[r.qid]: r.answer for r in rs2}
        for spec in by_spec:
            np.testing.assert_array_equal(by_spec[spec],
                                          by_spec2[spec])

    def test_fleet_ingest_between_compact_and_refresh(self, g,
                                                      tmp_path):
        """REGRESSION (serve.Server's wedge, fleet leg): a mutation
        between compact() and refresh_live() with a reproducible
        push query centrally queued must not wedge the fleet — the
        query pinned the new base_epoch, which the delta mask
        replays."""
        lg = LiveGraph(g, capacity=64)
        flt = self._fleet(g, lg, tmp_path)
        flt.warm(["sssp"])
        lg.append_edges([1], [2])
        lg.compact(force=True)              # base_epoch -> 1
        flt.submit("sssp", source=3)        # admitted at epoch 1
        s1, d1 = _mutations(g.nv, 6, 92)
        flt.mutate(s1, d1)                  # epoch -> 2
        flt.refresh_live()                  # must NOT raise
        rs = flt.run()
        assert len(rs) == 1 and rs[0].epoch == 1
        assert check_live_answers(lg, rs) == 0

    def test_live_fleet_refuses_subprocess_replicas(self, g,
                                                    tmp_path):
        """A subprocess replica serves the static graph spec — in a
        live fleet its answers would wear epoch=None and evade the
        torn-epoch audit, so the spawn is a typed refusal."""
        lg = LiveGraph(g, capacity=8)
        flt = self._fleet(g, lg, tmp_path)
        with pytest.raises(ValueError, match="admission epoch"):
            flt.add_subprocess_replica({"kind": "rmat", "scale": 5})

    def test_cached_hits_skip_service_histogram(self, g, tmp_path):
        """REGRESSION: cache hits retire in ~0s without touching an
        engine; feeding them into fleet_service_seconds dragged down
        the mean the deadline-admission projection divides by, so
        tight-deadline queries that would really wait a full drain
        were admitted instead of shed typed."""
        lg = LiveGraph(g, capacity=32)
        flt = self._fleet(g, lg, tmp_path, cache=True)
        flt.submit("sssp", source=3)
        rs = flt.run()
        assert len(rs) == 1 and not rs[0].cached
        h = flt.metrics.histogram("fleet_service_seconds",
                                  kind="sssp")
        assert h.count == 1
        flt.submit("sssp", source=3)        # same key, same epoch
        rs2 = flt.run()
        assert len(rs2) == 1 and rs2[0].cached
        # the cached retirement must NOT add a ~0s sample
        assert h.count == 1

    def test_delta_full_sheds_typed(self, g, tmp_path):
        from lux_tpu import fleet
        ev = telemetry.EventLog()
        lg = LiveGraph(g, capacity=4)
        with telemetry.use(events=ev):
            flt = self._fleet(g, lg, tmp_path)
            with pytest.raises(fleet.AdmissionError) as ei:
                flt.mutate(*_mutations(g.nv, 6, 73))
            assert ei.value.reason == fleet.SHED_DELTA_FULL
            assert ei.value.qid in {e.qid for e in flt.shed_records}
        sheds = [e for e in ev.events if e["kind"] == "query_shed"]
        assert sheds and sheds[0]["reason"] == "delta_full"


# ---------------------------------------------------------------------
# round 21: the mutation algebra — v2 WAL records, version compat


class TestMutationAlgebraLog:
    def test_wal_v2_roundtrip_bitwise(self, gw, tmp_path):
        """Deletes + reweights journal as v2 records and recover
        BITWISE — including the d_kind block, the op counters, and
        the pending-anti admission cap."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(gw, capacity=64, wal_path=wal)
        s1, d1 = _mutations(gw.nv, 5, 1)
        rng = np.random.default_rng(7)
        lg.append_edges(s1, d1,
                        rng.uniform(0.5, 4.0, 5).astype(np.float32))
        es, ed = _live_edge(gw, 3)
        lg.delete_edges([es], [ed])
        rs, rd = _live_edge(gw, 10)
        lg.reweight_edges([rs], [rd], [2.25])
        assert lg.deletions == 1 and lg.reweights == 1
        assert lg.anti_pending() == 2
        want = _wal_state(lg)
        lg.close()
        lg2 = LiveGraph.recover(gw, wal)
        _assert_state_equal(_wal_state(lg2), want)
        # recovery restores the ANTI ledger: admission stays capped
        # below the earliest pending deletion for BOTH families
        assert lg2.anti_pending() == 2
        assert lg2.view_epoch("push") == 1
        assert lg2.view_epoch("pull") == 1
        # the oracle surfaces agree bitwise at every epoch
        for e in range(lg2.epoch + 1):
            a, b = lg.graph_at(e), lg2.graph_at(e)
            np.testing.assert_array_equal(a.col_idx, b.col_idx)
            np.testing.assert_array_equal(a.weights, b.weights)
        lg2.close()

    def test_wal_v1_replays_bitwise_under_v2_reader(self, g,
                                                    tmp_path):
        """Version compat: a v1 (round-20, append-only) log replays
        bitwise under the round-21 reader, the recovered log RESUMES
        at the HEADER's version, and the v2 kinds refuse typed
        against it — never silently journaling a record a v1 reader
        would reject as corruption."""
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 16, version=1)
        log.append_edge(1, 1, 2, 0)
        log.append_edge(2, 3, 4, 0)
        log.close()
        assert luxfmt.read_wal_header(wal, nv=g.nv)[2] == 1
        lg = LiveGraph.recover(g, wal)
        assert lg.count == 2 and lg.epoch == 2
        np.testing.assert_array_equal(lg.d_src[:2], [1, 3])
        np.testing.assert_array_equal(lg.d_kind[:2], [0, 0])
        assert lg.anti_pending() == 0
        # appends keep chaining onto the resumed v1 log ...
        lg.append_edges([5], [6])
        assert lg.epoch == 3
        # ... but the v2 mutation kinds refuse typed (the kind set
        # is part of the header version's contract)
        with pytest.raises(MutationLogError) as ei:
            lg.delete_edges([1], [2])
        assert ei.value.check == "record_kind"
        # the refusal journaled NOTHING: state unchanged, replayable
        assert lg.epoch == 3 and lg.deletions == 0
        lg.close()
        lg2 = LiveGraph.recover(g, wal)
        assert lg2.count == 3 and lg2.epoch == 3
        lg2.close()

    def test_v2_kind_inside_v1_header_is_corruption(self, g,
                                                    tmp_path):
        """A DELETE record inside a v1-headed log at rest is typed
        record_kind corruption — scan enforces the header version's
        kind set, so a v1 reader and the v2 reader agree the file is
        bad rather than disagreeing on its meaning."""
        from lux_tpu.livegraph import REC_DELETE, _pack_record
        wal = str(tmp_path / "g.lux.wal")
        log = MutationLog(wal, g.nv, 16, version=1)
        log.append_edge(1, 1, 2, 0)
        log._append(_pack_record(2, REC_DELETE, 1, 2, 0, log._crc))
        log.close()
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "record_kind"

    @pytest.mark.parametrize("op", ["delete", "reweight"])
    def test_torn_tail_and_rot_per_new_kind(self, gw, tmp_path, op):
        """Per new record kind: a torn tail is recoverable (strict
        prefix, truncated deterministically), a FULL-SIZE bad-CRC
        final record is hard corruption — same taxonomy as the
        round-20 append records."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(gw, capacity=16, wal_path=wal)
        s0, d0 = _live_edge(gw, 2)
        if op == "delete":
            lg.delete_edges([s0], [d0])
        else:
            lg.reweight_edges([s0], [d0], [3.5])
        want = _wal_state(lg)
        lg.close()
        good = open(wal, "rb").read()
        faults.tear_wal(wal, keep_bytes=9)
        recs, _nv, _cap, torn = MutationLog.scan(wal, nv=gw.nv)
        assert len(recs) == 1 and torn == 9
        lg2 = LiveGraph.recover(gw, wal)
        _assert_state_equal(_wal_state(lg2), want)
        assert lg2.anti_pending() == 1
        lg2.close()
        # full-size rot INSIDE the mutation record: typed crc_chain
        blob = bytearray(good)
        blob[-10] ^= 0xFF
        open(wal, "wb").write(bytes(blob))
        with pytest.raises(MutationLogError) as ei:
            MutationLog.scan(wal)
        assert ei.value.check == "crc_chain"

    @pytest.mark.parametrize("action,op", [
        (faults.MUT_DELETE, "delete"),
        (faults.MUT_REWEIGHT, "reweight")])
    def test_mut_delete_reweight_crash_legs(self, gw, tmp_path,
                                            action, op):
        """The op-asserting crash legs: the injected crash lands
        BEFORE the WAL record — recovery is bitwise the pre-batch
        state with the anti ledger intact — and a plan written
        against the wrong op refuses loudly instead of drilling a
        different stream than intended."""
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(schedule={1: action})
        lg = LiveGraph(gw, capacity=16, wal_path=wal, fault=plan)
        lg.append_edges([1], [2], [1.0])
        want = _wal_state(lg)
        s0, d0 = _live_edge(gw, 4)
        with pytest.raises(faults.InjectedWorkerCrash):
            if op == "delete":
                lg.delete_edges([s0], [d0])
            else:
                lg.reweight_edges([s0], [d0], [2.0])
        assert plan.fired == [(1, action)]
        assert lg.anti_pending() == 0
        lg.close()
        lg2 = LiveGraph.recover(gw, wal)
        _assert_state_equal(_wal_state(lg2), want)
        lg2.close()
        # the op-assert arm: an append firing where the plan
        # scheduled a delete/reweight crash is a drill-script bug
        plan2 = faults.MutationFaultPlan(schedule={0: action})
        lg3 = LiveGraph(gw, capacity=16, fault=plan2)
        with pytest.raises(ValueError, match="expects"):
            lg3.append_edges([3], [4], [1.0])

    def test_fsck_reports_v2_mutation_mix(self, gw, g, tmp_path):
        """scripts/fsck_lux.py renders the v2 mutation mix; a v1 log
        reports its version with no phantom algebra counters."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(gw, capacity=16, wal_path=wal)
        lg.append_edges([1], [2], [1.5])
        ds, dd = _live_edge(gw, 0)
        lg.delete_edges([ds], [dd])
        rs, rd = _live_edge(gw, 5)
        lg.reweight_edges([rs], [rd], [0.75])
        lg.close()
        r = subprocess.run([sys.executable, str(FSCK), wal],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "OK wal v2" in r.stdout
        assert "deletes=1 reweights=1" in r.stdout
        wal1 = str(tmp_path / "v1.lux.wal")
        log = MutationLog(wal1, g.nv, 8, version=1)
        log.append_edge(1, 1, 2, 0)
        log.close()
        r = subprocess.run([sys.executable, str(FSCK), wal1],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "OK wal v1" in r.stdout
        assert "deletes=" not in r.stdout


# ---------------------------------------------------------------------
# round 21: deletions / reweights on the live graph


class TestMutationAlgebraLive:
    def test_delete_validation_typed(self, g, gw):
        lg = LiveGraph(g, capacity=8)
        ps, pd = _phantom_edge(g)
        with pytest.raises(ValueError, match="live edge"):
            lg.delete_edges([ps], [pd])
        assert lg.epoch == 0 and lg.count == 0
        # deletions CONSUME multiplicity: a batch deleting one edge
        # more often than it lives refuses whole
        us, ud = _live_edge(g, 0)
        k = int(np.sum((g.edge_arrays()[0] == us)
                       & (g.edge_arrays()[1] == ud)))
        with pytest.raises(ValueError, match="live edge"):
            lg.delete_edges([us] * (k + 1), [ud] * (k + 1))
        assert lg.epoch == 0
        # reweight on an unweighted base refuses before journaling
        with pytest.raises(ValueError, match="UNWEIGHTED"):
            lg.reweight_edges([us], [ud], [2.0])
        lw = LiveGraph(gw, capacity=8)
        with pytest.raises(ValueError, match="weights"):
            lw.reweight_edges([us], [ud], None)

    def test_tombstones_consume_delta_capacity(self, g):
        lg = LiveGraph(g, capacity=2)
        s0, d0 = _live_edge(g, 0)
        s1, d1 = _live_edge(g, 1)
        lg.delete_edges([s0], [d0])
        lg.append_edges([1], [2])
        with pytest.raises(DeltaFullError):
            lg.delete_edges([s1], [d1])
        assert lg.epoch == 2 and lg.count == 2

    def test_graph_at_and_compact_fold_deletions(self, gw,
                                                 tmp_path):
        """The deterministic fold: graph_at (the oracle surface),
        compact (the live base), and recover (the crash path) all
        run _apply_ops, so all three agree bitwise on which edge a
        deletion tombstones and which a reweight restates."""
        wal = str(tmp_path / "g.lux.wal")
        lg = LiveGraph(gw, capacity=16, wal_path=wal)
        s0, d0 = _live_edge(gw, 6)
        lg.delete_edges([s0], [d0])                 # epoch 1
        # a MULTIPLICITY-1 edge, so the restatement is unambiguous
        # (duplicate pairs would leave "which instance" to the
        # deterministic targeting rule, fine for the fold-equality
        # checks below but not for a direct weight assertion)
        sa, da = gw.edge_arrays()
        uniq = next(i for i in range(gw.ne)
                    if (sa[i], da[i]) != (s0, d0)
                    and np.sum((sa == sa[i]) & (da == da[i])) == 1)
        rs, rd = int(sa[uniq]), int(da[uniq])
        lg.reweight_edges([rs], [rd], [9.0])        # epoch 2
        g1, g2 = lg.graph_at(1), lg.graph_at(2)
        assert g1.ne == gw.ne - 1 and g2.ne == gw.ne - 1
        s2, d2 = g2.edge_arrays()
        m = (s2 == rs) & (d2 == rd)
        assert m.sum() == 1
        assert np.isclose(float(np.asarray(g2.weights)[m][0]), 9.0)
        gen = lg.compact(force=True)
        assert gen == 1 and lg.anti_pending() == 0
        np.testing.assert_array_equal(lg.base.col_idx, g2.col_idx)
        np.testing.assert_array_equal(lg.base.weights, g2.weights)
        assert lg.view_epoch("push") == 2
        lg.close()
        lg2 = LiveGraph.recover(gw, wal)
        assert lg2.generation == 1 and lg2.anti_pending() == 0
        np.testing.assert_array_equal(lg2.base.col_idx,
                                      lg.base.col_idx)
        np.testing.assert_array_equal(lg2.base.weights,
                                      lg.base.weights)
        lg2.close()

    def test_view_epoch_caps_both_families_until_fold(self, g):
        lg = LiveGraph(g, capacity=16)
        lg.append_edges([1], [2])                   # epoch 1
        s0, d0 = _live_edge(g, 0)
        lg.delete_edges([s0], [d0])                 # epoch 2 (anti)
        lg.append_edges([3], [4])                   # epoch 3
        for fam in ("push", "pull"):
            assert lg.view_epoch(fam) == 1
        lg.compact(force=True)
        for fam in ("push", "pull"):
            assert lg.view_epoch(fam) == 3


# ---------------------------------------------------------------------
# round 21: decremental oracles — proved equal to full recompute


class TestDecrementalOracles:
    @pytest.mark.parametrize("n_del,seed", [(1, 24), (5, 25),
                                            (40, 26)])
    def test_sssp_decremental_equals_full(self, g, n_del, seed):
        rng = np.random.default_rng(seed)
        src, dst = g.edge_arrays()
        idx = rng.choice(g.ne, size=n_del, replace=False)
        keep = np.ones(g.ne, bool)
        keep[idx] = False
        g_new = Graph.from_edges(src[keep], dst[keep], g.nv)
        d0 = sssp.reference_sssp(g, 0)
        dec = sssp.reference_sssp_decremental(g_new, d0, dst[idx])
        np.testing.assert_array_equal(
            _clamp_ref(dec), _clamp_ref(sssp.reference_sssp(g_new,
                                                            0)))

    @pytest.mark.parametrize("n_mut,seed", [(3, 34), (25, 35)])
    def test_sssp_weighted_reweight_equals_full(self, gw, n_mut,
                                                seed):
        """Weight updates in BOTH directions (increases degrade the
        fixed point, decreases improve it) repair to exactly the
        full recompute through the same cone rule."""
        rng = np.random.default_rng(seed)
        src, dst = gw.edge_arrays()
        idx = rng.choice(gw.ne, size=n_mut, replace=False)
        w_new = np.asarray(gw.weights).copy()
        w_new[idx] = rng.uniform(0.25, 8.0,
                                 size=n_mut).astype(np.float32)
        g_new = Graph.from_edges(src, dst, gw.nv, weights=w_new)
        d0 = sssp.reference_sssp(gw, 0, weighted=True)
        dec = sssp.reference_sssp_decremental(
            g_new, d0, dst[idx], weighted=True)
        np.testing.assert_allclose(
            dec, sssp.reference_sssp(g_new, 0, weighted=True),
            rtol=1e-6)

    @pytest.mark.parametrize("n_del,seed", [(1, 44), (7, 45),
                                            (40, 46)])
    def test_components_decremental_equals_full(self, g, n_del,
                                                seed):
        rng = np.random.default_rng(seed)
        src, dst = g.edge_arrays()
        idx = rng.choice(g.ne, size=n_del, replace=False)
        keep = np.ones(g.ne, bool)
        keep[idx] = False
        g_new = Graph.from_edges(src[keep], dst[keep], g.nv)
        c0 = components.reference_components(g)
        dec = components.reference_components_decremental(
            g_new, c0, dst[idx])
        np.testing.assert_array_equal(
            dec, components.reference_components(g_new))


# ---------------------------------------------------------------------
# round 21: the anti-monotone re-seed on device


class TestReseed:
    def _deleted(self, lg, g, rng, n):
        """Delete n distinct base edges; returns their dst."""
        src, dst = g.edge_arrays()
        idx = rng.choice(g.ne, size=n, replace=False)
        lg.delete_edges(src[idx], dst[idx])
        return dst[idx]

    def test_sssp_deletion_reseed_bitwise(self, g):
        """Converged push state + deletions -> revalidate dispatches
        to the cone re-seed and lands BITWISE on full recompute at
        the target epoch (== the decremental oracle)."""
        import jax
        eng0 = sssp.build_engine(g, 0, num_parts=2)
        lab, act = eng0.init_state()
        lab, act, _ = eng0.converge(lab, act)
        dist0 = eng0.sg.from_padded(np.asarray(jax.device_get(lab)))
        lg = LiveGraph(g, capacity=32)
        rng = np.random.default_rng(57)
        touched = self._deleted(lg, g, rng, 5)
        g_new = lg.graph_at(lg.epoch)
        # CONTRACT: the re-seed engine is built over graph_at(target)
        eng = sssp.build_engine(g_new, 0, num_parts=2)
        lab1, act1 = eng.place(
            eng.sg.to_padded(dist0),
            eng.sg.to_padded(np.zeros(g.nv, bool)))
        lab1, act1, _ = lg.revalidate(eng, lab1, act1)
        assert lg.reseeds == 1
        full = _clamp_ref(sssp.reference_sssp(g_new, 0))
        dec = _clamp_ref(sssp.reference_sssp_decremental(
            g_new, _clamp_ref(sssp.reference_sssp(g, 0)), touched))
        np.testing.assert_array_equal(dec, full)
        np.testing.assert_array_equal(_sssp_host(eng, lab1), full)

    def test_sssp_weighted_reweight_reseed(self, gw):
        import jax
        eng0 = sssp.build_engine(gw, 0, num_parts=2, weighted=True)
        lab, act = eng0.init_state()
        lab, act, _ = eng0.converge(lab, act)
        d0 = eng0.sg.from_padded(np.asarray(jax.device_get(lab)))
        lg = LiveGraph(gw, capacity=32)
        rng = np.random.default_rng(58)
        src, dst = gw.edge_arrays()
        idx = rng.choice(gw.ne, size=4, replace=False)
        # both directions: two raises, two improvements
        w_new = np.concatenate([
            rng.uniform(4.5, 8.0, 2),
            rng.uniform(0.1, 0.4, 2)]).astype(np.float32)
        lg.reweight_edges(src[idx], dst[idx], w_new)
        g_new = lg.graph_at(1)
        eng = sssp.build_engine(g_new, 0, num_parts=2,
                                weighted=True)
        lab1, act1 = eng.place(
            eng.sg.to_padded(d0),
            eng.sg.to_padded(np.zeros(gw.nv, bool)))
        lab1, act1, _ = lg.revalidate(eng, lab1, act1)
        h = eng.sg.from_padded(np.asarray(jax.device_get(lab1)))
        ref = sssp.reference_sssp(g_new, 0, weighted=True)
        reach = np.isfinite(ref)
        np.testing.assert_allclose(h[reach], ref[reach], rtol=1e-5)
        assert not np.isfinite(h[~reach]).any()

    def test_components_deletion_reseed_bitwise(self, g):
        import jax
        eng0 = components.build_engine(g, num_parts=2)
        lab, act = eng0.init_state()
        lab, act, _ = eng0.converge(lab, act)
        c0 = eng0.sg.from_padded(np.asarray(jax.device_get(lab)))
        lg = LiveGraph(g, capacity=32)
        rng = np.random.default_rng(59)
        self._deleted(lg, g, rng, 5)
        g_new = lg.graph_at(lg.epoch)
        eng = components.build_engine(g_new, num_parts=2)
        lab1, act1 = eng.place(
            eng.sg.to_padded(c0),
            eng.sg.to_padded(np.zeros(g.nv, bool)))
        lab1, _, _ = lg.revalidate(eng, lab1, act1)
        h = eng.sg.from_padded(np.asarray(jax.device_get(lab1)))
        np.testing.assert_array_equal(
            h.astype(np.int64),
            components.reference_components(g_new))

    def test_cone_cap_falls_back_to_full_recompute(self, g):
        lg = LiveGraph(g, capacity=32, cone_cap=1 / g.nv)
        rng = np.random.default_rng(60)
        self._deleted(lg, g, rng, 2)
        g_new = lg.graph_at(lg.epoch)
        eng = sssp.build_engine(g_new, 0, num_parts=2)
        lab, act = eng.init_state()
        lab, act, _ = lg.revalidate(eng, lab, act)
        assert lg.reseeds == 1 and lg.reseed_fallbacks == 1
        np.testing.assert_array_equal(
            _sssp_host(eng, lab),
            _clamp_ref(sssp.reference_sssp(g_new, 0)))

    def test_reseed_crash_leaves_anti_pending(self, g):
        """The RESEED_CRASH leg: the crash lands between the cone
        computation and the re-converge — no answer was produced
        from the half-re-seeded state, the anti ledger is intact,
        admission stays capped, and the retry completes bitwise."""
        plan = faults.MutationFaultPlan(
            reseed_schedule={0: faults.RESEED_CRASH})
        lg = LiveGraph(g, capacity=32, fault=plan)
        s0, d0 = _live_edge(g, 4)
        lg.delete_edges([s0], [d0])
        g_new = lg.graph_at(1)
        eng = sssp.build_engine(g_new, 0, num_parts=2)
        lab, act = eng.init_state()
        with pytest.raises(faults.InjectedWorkerCrash):
            lg.revalidate(eng, lab, act)
        assert plan.fired == [(0, faults.RESEED_CRASH)]
        assert lg.reseeds == 0 and lg.anti_pending() == 1
        assert lg.view_epoch("push") == 0
        # the retry (schedule exhausted) converges to full recompute
        lab, act = eng.init_state()
        lab, act, _ = lg.revalidate(eng, lab, act)
        assert lg.reseeds == 1
        np.testing.assert_array_equal(
            _sssp_host(eng, lab),
            _clamp_ref(sssp.reference_sssp(g_new, 0)))

    def test_per_column_targets_cannot_cross_anti_epoch(self, g):
        from lux_tpu.livegraph import LiveGraphError
        lg = LiveGraph(g, capacity=32)
        lg.append_edges([1], [2])
        s0, d0 = _live_edge(g, 0)
        lg.delete_edges([s0], [d0])             # anti at epoch 2
        eng = sssp.build_engine(g, num_parts=2, sources=[3, 17])
        lab, act = eng.init_state()
        with pytest.raises(LiveGraphError, match="anti-monotone"):
            lg.revalidate(eng, lab, act,
                          col_epoch=np.array([1, 2], np.int32))


# ---------------------------------------------------------------------
# round 21: the economics-driven compaction scheduler


class TestCompactionScheduler:
    def test_decision_ladder(self, g):
        """Every leg of the decision order, in order: empty ->
        admitted -> slo_burn -> anti_monotone -> occupancy -> drag
        -> idle."""
        from lux_tpu.livegraph import CompactionScheduler
        lg = LiveGraph(g, capacity=64, compact_threshold=0.5)
        sched = CompactionScheduler(lg, burn=lambda: 0.0)
        d = sched.decide()
        assert (d["action"], d["reason"]) == ("none", "empty")
        lg.append_edges([1], [2])
        d = sched.decide()
        assert (d["action"], d["reason"]) == ("none", "idle")
        # economics ride on every decision
        for f in ("occupancy", "threshold", "delta_count",
                  "anti_pending", "drag_ns", "drag_source",
                  "admitted", "pins", "burn"):
            assert f in d
        lg.admit("push")
        assert sched.decide()["reason"] == "admitted"
        lg.release()
        # slo burn defers non-urgent folds
        s0, d0 = _live_edge(g, 3)
        lg.delete_edges([s0], [d0])
        hot = CompactionScheduler(lg, burn=lambda: 0.9)
        assert hot.decide()["reason"] == "slo_burn"
        # anti-monotone pressure folds at the first quiet window
        d = sched.decide()
        assert (d["action"], d["reason"]) == ("compact",
                                              "anti_monotone")
        r = sched.maybe_compact()
        assert r["action"] == "compact" and r["generation"] == 1
        assert sched.scheduler_compactions == 1
        assert lg.anti_pending() == 0 and lg.count == 0
        # occupancy trigger
        for i in range(33):
            lg.append_edges([i % g.nv], [(i + 1) % g.nv])
        d = sched.decide()
        assert (d["action"], d["reason"]) == ("compact", "occupancy")
        # measured drag trigger (below threshold, standing drag)
        lg2 = LiveGraph(g, capacity=4096, compact_threshold=0.99)
        lg2.append_edges(np.arange(10) % g.nv,
                         (np.arange(10) + 1) % g.nv)
        lg2.record_drag_sample(1e-3, 10)    # 1e5 ns/slot
        sched2 = CompactionScheduler(lg2)
        d = sched2.decide()
        assert (d["action"], d["reason"]) == ("compact", "drag")
        assert d["drag_source"] == "measured"

    def test_pin_race_demotes_to_deferral(self, g):
        from lux_tpu.livegraph import CompactionScheduler

        class Racy(CompactionScheduler):
            def decide(self):
                d = super().decide()
                if d["action"] == "compact":
                    self.live.pin()     # the race window
                return d

        lg = LiveGraph(g, capacity=8)
        s0, d0 = _live_edge(g, 0)
        lg.delete_edges([s0], [d0])
        sched = Racy(lg)
        d = sched.maybe_compact()
        assert (d["action"], d["reason"]) == ("defer", "pin_race")
        assert sched.scheduler_compactions == 0
        lg.unpin()

    def test_scheduler_soak_mesh8(self, g):
        """THE round-21 scheduler acceptance: seeded Poisson mixed
        traffic (all three kinds) + a live mutation stream with
        deletions on mesh8, the scheduler alone deciding folds —
        occupancy stays bounded, ZERO delta_full sheds, at least
        one scheduler compaction fires, and every admitted answer
        equals its oracle at its admission epoch."""
        from lux_tpu import serve
        from lux_tpu.livegraph import CompactionScheduler
        from lux_tpu.parallel.mesh import make_mesh

        lg = LiveGraph(g, capacity=48, compact_threshold=0.5)
        srv = serve.Server(g, batch=2, num_parts=8,
                           mesh=make_mesh(8), live=lg, seg_iters=4)
        sched = CompactionScheduler(lg, burn=srv.slo_burn)
        rng = np.random.default_rng(67)
        kinds = ["sssp", "components", "pagerank"]
        appended: list = []
        responses = []
        peak_occ = 0.0
        for step in range(8):
            for _ in range(int(rng.poisson(3)) + 1):
                srv.submit(rng.choice(kinds),
                           source=int(rng.integers(g.nv)))
            n = int(rng.poisson(5)) + 1
            s, d = rng.integers(g.nv, size=n), rng.integers(
                g.nv, size=n)
            srv.mutate(s, d)            # zero delta_full sheds: a
            appended += list(zip(s.tolist(), d.tolist()))
            if step in (2, 5):          # deletions in the stream
                es, ed = appended.pop(0)
                srv.mutate([es], [ed], op="delete")
            peak_occ = max(peak_occ, lg.occupancy())
            responses += srv.run()
            sched.maybe_compact(server=srv)
        assert peak_occ < 1.0
        assert sched.scheduler_compactions >= 1
        assert lg.deletions == 2
        assert check_live_answers(lg, responses) == 0
        # the trail is coherent: every fold the scheduler ran is a
        # real compaction, and deferrals never exceeded decisions
        assert lg.compactions == sched.scheduler_compactions


# ---------------------------------------------------------------------
# THE chaos acceptance


class TestLiveChaosAcceptance:
    def test_mutation_stream_kill_and_compact_crash_mesh8(
            self, g, tmp_path):
        """Oversubscribed mixed-kind open-loop load on the 8-virtual-
        device mesh + a live mutation stream concurrent with the
        drain + replica r1 killed mid-drain + an injected crash
        mid-compaction.  Every admitted answer equals its NumPy
        oracle at its ADMISSION epoch (bitwise for the integer apps),
        zero torn reads, zero duplicate retirements, WAL replay
        bitwise-identical, and the event trail (with the torn-epoch
        audit armed) renders clean."""
        import contextvars

        import loadgen

        from lux_tpu import fleet, resilience
        from lux_tpu.parallel.mesh import make_mesh

        kinds = ["sssp", "components", "pagerank"]
        slo = {k: 60000.0 for k in kinds}
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(
            compact_schedule={0: faults.COMPACT_CRASH})
        live = LiveGraph(g, capacity=96, wal_path=wal, fault=plan,
                         compact_threshold=0.5)
        path = tmp_path / "live_chaos_ev.jsonl"
        ev = telemetry.EventLog(str(path))
        with telemetry.use(events=ev):
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="live-fleet", file="<test>", mesh=8)
            t0 = time.perf_counter()
            flt = fleet.FleetServer(
                g, live=live, cache=True, replicas=2, batch=2,
                num_parts=8, mesh=make_mesh(8), slo_ms=slo,
                retry=resilience.RetryPolicy(retries=3,
                                             backoff_s=0.01,
                                             max_backoff_s=0.05,
                                             jitter_seed=0),
                board_path=str(tmp_path / "board"))
            flt.warm(kinds)
            flt.mutate(*_mutations(g.nv, 8, 81))   # epoch 1 pre-load
            kill = faults.ReplicaKillPlan({"r1": 1})
            flt.set_fault(kill)

            # the LIVE mutation stream: ingest concurrent with the
            # drain (appends take the LiveGraph lock; published slots
            # are immutable; epoch advances last — the torn-read-free
            # construction this drill exercises)
            stop = threading.Event()
            mrng = np.random.default_rng(82)

            def mutator():
                # stream until the load ends, leaving headroom under
                # the threshold so the post-load top-up controls the
                # exact trigger point.  Pace by OBSERVED drain
                # progress (new query_start/query_done events in the
                # in-memory trail — append-only, len() is a safe
                # probe) rather than a wall-clock sleep: under CI
                # load a timed cadence either starves the stream or
                # outruns the drain.  stop.wait is a poll tick only.
                seen = len(ev.events)
                while not stop.is_set() and live.occupancy() < 0.4:
                    now = len(ev.events)
                    progressed = any(
                        e.get("kind") in ("query_start", "query_done")
                        for e in ev.events[seen:now])
                    seen = now
                    if not progressed:
                        stop.wait(0.005)
                        continue
                    try:
                        flt.mutate(mrng.integers(g.nv, size=4),
                                   mrng.integers(g.nv, size=4))
                    except fleet.AdmissionError:
                        break       # delta_full: typed backpressure

            ctx = contextvars.copy_context()
            mth = threading.Thread(
                target=lambda: ctx.run(mutator), daemon=True)
            mth.start()
            rng = np.random.default_rng(83)
            rep = loadgen.run_step(flt, rate=500.0, n=14,
                                   kinds=kinds, rng=rng, step=0)
            stop.set()
            mth.join(timeout=10.0)

            # top the stream up past the compaction trigger (the
            # drain may have outrun the mutator's cadence)
            while not live.should_compact():
                flt.mutate(mrng.integers(g.nv, size=4),
                           mrng.integers(g.nv, size=4))
            # crash mid-compaction (between drains, nothing pinned)
            assert live.should_compact()
            with pytest.raises(faults.InjectedWorkerCrash):
                live.compact()
            pre_crash = _wal_state(live)
            live.close()

            # recovery: bitwise-identical WAL replay
            live2 = LiveGraph.recover(g, wal)
            _assert_state_equal(_wal_state(live2), pre_crash)
            # ... and the recovered generation completes the fold +
            # keeps serving: a fresh fleet over the compacted base
            assert live2.compact(force=True) == 1
            # a NEW run boundary: the recovered fleet restarts its
            # qid space, exactly like a recovered process would
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="live-fleet-recovered", file="<test>",
                    mesh=8)
            flt2 = fleet.FleetServer(
                live2.base, live=live2, cache=True, replicas=2,
                batch=2, num_parts=8, mesh=make_mesh(8), slo_ms=slo,
                board_path=str(tmp_path / "board2"))
            post = []
            for kind in kinds:
                flt2.submit(kind, source=9)
            post = flt2.run()
            ev.emit("run_done",
                    seconds=round(time.perf_counter() - t0, 6),
                    iters=rep.served + len(post))
        ev.close()

        # the kill fired mid-drain and queries failed over
        assert kill.fired and kill.fired[0][0] == "r1"
        assert flt.failovers >= 1
        # the mutation stream really ran DURING the load
        assert live2.mutations > 8
        # admitted + shed partition the load; exactly-once retirement
        assert rep.drained
        assert rep.served + rep.shed == rep.submitted
        qids = [r.qid for r in rep.responses]
        assert len(set(qids)) == len(qids)
        assert flt.dup_dropped == 0
        # every admitted answer equals its oracle AT ITS ADMISSION
        # EPOCH — bitwise for sssp/components (check_live_answers
        # uses array_equal there), including the failed-over ones
        assert check_live_answers(live2, rep.responses) == 0
        assert check_live_answers(live2, post) == 0
        # zero torn reads: the events trail carries epoch +
        # answer_epoch on every live answer and the summary's
        # torn-epoch audit (+ compaction bracket + replay regression
        # rules) must pass
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "live graph:" in r.stdout
        assert "WAL replay:" in r.stdout
        assert "replicas: 2 up, 1 lost (r1)" in r.stdout
        live2.close()

    def test_mutation_algebra_chaos_mesh8(self, g, tmp_path):
        """THE round-21 chaos acceptance: the FULL mutation algebra
        under fire on mesh8 — deletions in the live stream, a
        replica killed mid-drain, an injected crash MID-RE-SEED and
        another mid-compaction, WAL replay bitwise with the anti
        ledger intact, the retried re-seed bitwise-equal to both the
        full recompute and the decremental oracle, the scheduler
        completing the crashed fold, and every admitted answer
        oracle-equal at its admission epoch with the events trail
        (re-seed pairing + scheduler economics audits armed)
        rendering clean."""
        import loadgen

        from lux_tpu import fleet, resilience
        from lux_tpu.livegraph import CompactionScheduler
        from lux_tpu.parallel.mesh import make_mesh

        kinds = ["sssp", "components", "pagerank"]
        slo = {k: 60000.0 for k in kinds}
        wal = str(tmp_path / "g.lux.wal")
        plan = faults.MutationFaultPlan(
            compact_schedule={0: faults.COMPACT_CRASH},
            reseed_schedule={0: faults.RESEED_CRASH})
        live = LiveGraph(g, capacity=96, wal_path=wal, fault=plan,
                         compact_threshold=0.5)
        path = tmp_path / "algebra_chaos_ev.jsonl"
        ev = telemetry.EventLog(str(path))
        with telemetry.use(events=ev):
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="live-algebra", file="<test>", mesh=8)
            t0 = time.perf_counter()
            flt = fleet.FleetServer(
                g, live=live, cache=True, replicas=2, batch=2,
                num_parts=8, mesh=make_mesh(8), slo_ms=slo,
                retry=resilience.RetryPolicy(retries=3,
                                             backoff_s=0.01,
                                             max_backoff_s=0.05,
                                             jitter_seed=0),
                board_path=str(tmp_path / "board"))
            flt.warm(kinds)
            flt.mutate(*_mutations(g.nv, 8, 91))   # epoch 1
            s7, d7 = _live_edge(g, 7)
            flt.mutate([s7], [d7], op="delete")    # epoch 2 (anti)
            assert live.view_epoch("push") == 1
            kill = faults.ReplicaKillPlan({"r1": 1})
            flt.set_fault(kill)
            rng = np.random.default_rng(92)
            rep = loadgen.run_step(flt, rate=500.0, n=12,
                                   kinds=kinds, rng=rng, step=0)
            # admission NEVER crossed the pending deletion
            assert rep.drained
            assert all(r.epoch <= 1 for r in rep.responses)
            s31, d31 = _live_edge(g, 31)
            flt.mutate([s31], [d31], op="delete")  # epoch 3 (anti)

            # the HONEST re-seed: a standalone engine over
            # graph_at(3) — crash lands between cone and converge
            g3 = live.graph_at(3)
            eng = sssp.build_engine(g3, 0, num_parts=2)
            lab, act = eng.init_state()
            with pytest.raises(faults.InjectedWorkerCrash):
                live.revalidate(eng, lab, act)
            # no answer escaped the half-re-seeded state: ledger
            # intact, admission still capped, nothing counted
            assert live.anti_pending() == 2
            assert live.view_epoch("push") == 1
            assert live.reseeds == 0
            # the retry (schedule exhausted) lands bitwise on BOTH
            # the full recompute and the decremental oracle
            lab, act = eng.init_state()
            lab, act, _ = live.revalidate(eng, lab, act)
            got = _sssp_host(eng, lab)
            full = _clamp_ref(sssp.reference_sssp(g3, 0))
            dec = _clamp_ref(sssp.reference_sssp_decremental(
                g3, _clamp_ref(sssp.reference_sssp(live.graph_at(1),
                                                   0)),
                np.array([d7, d31])))
            np.testing.assert_array_equal(dec, full)
            np.testing.assert_array_equal(got, full)
            assert live.reseeds == 1

            # the scheduler sees the anti pressure; its first fold
            # hits the injected COMPACT_CRASH
            sched = CompactionScheduler(live, burn=flt.slo_burn)
            d = sched.decide()
            assert (d["action"], d["reason"]) == ("compact",
                                                  "anti_monotone")
            with pytest.raises(faults.InjectedWorkerCrash):
                live.compact(force=True)
            pre_crash = _wal_state(live)
            live.close()

            # recovery: bitwise replay, anti ledger restored
            live2 = LiveGraph.recover(g, wal)
            _assert_state_equal(_wal_state(live2), pre_crash)
            assert live2.anti_pending() == 2
            assert live2.deletions == 2
            # the scheduler completes the crashed fold on the
            # recovered log (schedule exhausted)
            sched2 = CompactionScheduler(live2)
            r2 = sched2.maybe_compact()
            assert r2["action"] == "compact"
            assert r2["generation"] == 1
            assert live2.anti_pending() == 0
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="live-algebra-recovered", file="<test>",
                    mesh=8)
            flt2 = fleet.FleetServer(
                live2.base, live=live2, cache=True, replicas=2,
                batch=2, num_parts=8, mesh=make_mesh(8),
                slo_ms=slo, board_path=str(tmp_path / "board2"))
            for kind in kinds:
                flt2.submit(kind, source=9)
            post = flt2.run()
            assert all(r.epoch == live2.epoch for r in post)
            ev.emit("run_done",
                    seconds=round(time.perf_counter() - t0, 6),
                    iters=rep.served + len(post))
        ev.close()

        assert kill.fired and kill.fired[0][0] == "r1"
        assert rep.served + rep.shed == rep.submitted
        qids = [r.qid for r in rep.responses]
        assert len(set(qids)) == len(qids)
        # every admitted answer oracle-equal at its admission epoch
        # — through two deletions, a kill, and two injected crashes
        assert check_live_answers(live2, rep.responses) == 0
        assert check_live_answers(live2, post) == 0
        # the trail renders clean with the round-21 audits armed:
        # re-seed pairing, scheduler economics, epoch regression
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "re-seed: 1 anti-monotone revalidation(s)" in r.stdout
        assert "compaction scheduler:" in r.stdout
        assert "delete" in r.stdout
        live2.close()
