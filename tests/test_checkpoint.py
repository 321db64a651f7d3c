"""Checkpoint/resume and profiling utilities (+ round-9 integrity:
per-leaf CRC32 verification, two-generation rotation, and the
corrupt-newest-generation fallback path)."""

import os

import numpy as np
import pytest

from lux_tpu import checkpoint as ckpt
from lux_tpu.apps import pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph


def test_save_load_roundtrip(tmp_path):
    p = str(tmp_path / "c.npz")
    state = (np.arange(6, dtype=np.float32).reshape(2, 3),
             np.array([True, False]))
    ckpt.save(p, state, {"iter": 7})
    leaves, meta = ckpt.load(p)
    assert meta == {"iter": 7}
    np.testing.assert_array_equal(leaves[0], state[0])
    np.testing.assert_array_equal(leaves[1], state[1])


def test_pull_checkpointed_matches_plain(tmp_path):
    src, dst = uniform_random_edges(100, 700, seed=61)
    g = Graph.from_edges(src, dst, 100)
    eng = pagerank.build_engine(g, num_parts=2)
    p = str(tmp_path / "pr.npz")

    want = eng.unpad(eng.run(eng.init_state(), 10))
    got_state = ckpt.run_checkpointed(eng, eng.init_state(), 10, p,
                                      segment=3)
    np.testing.assert_allclose(eng.unpad(got_state), want, rtol=1e-6)
    leaves, meta = ckpt.load(p)
    assert meta["iter"] == 10
    # resume from the iteration-6 structure: load and continue
    (state_arr,), meta = ckpt.load(p)
    assert np.isfinite(state_arr).all()


def test_push_converge_checkpointed_resume(tmp_path):
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    p = str(tmp_path / "ss.npz")

    want, _ = sssp.run(g, start_vertex=0, num_parts=2)

    # run only 2 iterations' worth of segments, then "crash"
    l, a, total = ckpt.converge_checkpointed(eng, p, segment=2,
                                             max_iters=2)
    assert os.path.exists(p) and total == 2
    # resume to convergence
    l, a, total = ckpt.converge_checkpointed(eng, p, segment=3,
                                             resume=True)
    got = eng.unpad(l)
    reach = ~sssp.unreachable(got)
    np.testing.assert_array_equal(got[reach], want[reach])


# -- integrity + generation fallback (round 9) -------------------------

def test_save_rotates_two_generations(tmp_path):
    p = str(tmp_path / "g.npz")
    state = (np.arange(4, dtype=np.float32),)
    ckpt.save(p, state, {"iter": 1})
    assert not os.path.exists(ckpt.prev_path(p))
    ckpt.save(p, state, {"iter": 2})
    assert ckpt.load(p)[1]["iter"] == 2
    assert ckpt.load(ckpt.prev_path(p))[1]["iter"] == 1
    ckpt.save(p, state, {"iter": 3})
    assert ckpt.load(ckpt.prev_path(p))[1]["iter"] == 2   # rolls
    assert ckpt.any_generation(p)
    ckpt.remove(p)
    assert not ckpt.any_generation(p)


def test_load_catches_bitflip(tmp_path):
    """A zip-valid payload bit flip — exactly what the container's own
    member CRC canNOT catch — fails the per-leaf CRC32."""
    from lux_tpu import faults

    p = str(tmp_path / "c.npz")
    ckpt.save(p, (np.arange(8, dtype=np.float32),), {"iter": 3})
    faults.bitflip_checkpoint(p)
    with pytest.raises(ckpt.CorruptCheckpointError, match="CRC32"):
        ckpt.load(p)


def test_load_wraps_truncated_and_garbage(tmp_path):
    """Truncated/garbage containers raise the TYPED error (never a
    raw zipfile.BadZipFile / KeyError), so resilience.classify routes
    them to generation fallback, not the deterministic-OSError fatal
    bucket.  A MISSING file stays FileNotFoundError."""
    from lux_tpu import faults

    p = str(tmp_path / "c.npz")
    ckpt.save(p, (np.arange(8, dtype=np.float32),), {"iter": 3})
    faults.truncate_checkpoint(p)
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt.load(p)
    with open(p, "w") as f:
        f.write("not a checkpoint at all")
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt.load(p)
    with pytest.raises(FileNotFoundError):
        ckpt.load(str(tmp_path / "never.npz"))


def test_load_any_falls_back_one_generation(tmp_path):
    from lux_tpu import faults, telemetry

    p = str(tmp_path / "c.npz")
    state = (np.arange(8, dtype=np.float32),)
    ckpt.save(p, state, {"iter": 5})
    ckpt.save(p, state, {"iter": 10})
    faults.bitflip_checkpoint(p)
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        leaves, meta, used = ckpt.load_any(p)
    assert meta["iter"] == 5 and used == ckpt.prev_path(p)
    np.testing.assert_array_equal(leaves[0], state[0])
    fb = [e for e in ev.events if e["kind"] == "checkpoint_fallback"]
    assert len(fb) == 1 and fb[0]["path"] == p
    # the corrupt newest is QUARANTINED: a repeat load_any reads the
    # good generation without re-reporting, and the next save's
    # rotation cannot promote the corrupt file over the good one
    assert not os.path.exists(p) and os.path.exists(ckpt.corrupt_path(p))
    with telemetry.use(events=ev):
        _l, meta2, _u = ckpt.load_any(p)
    assert meta2["iter"] == 5
    assert sum(e["kind"] == "checkpoint_fallback"
               for e in ev.events) == 1
    ckpt.save(p, state, {"iter": 20})
    assert ckpt.load(p)[1]["iter"] == 20
    assert ckpt.load(ckpt.prev_path(p))[1]["iter"] == 5   # still good
    ckpt.remove(p)
    assert not os.path.exists(ckpt.corrupt_path(p))


def test_load_any_both_generations_corrupt_raises(tmp_path):
    from lux_tpu import faults

    p = str(tmp_path / "c.npz")
    state = (np.arange(8, dtype=np.float32),)
    ckpt.save(p, state, {"iter": 5})
    ckpt.save(p, state, {"iter": 10})
    faults.bitflip_checkpoint(p)
    faults.truncate_checkpoint(ckpt.prev_path(p))
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt.load_any(p)


def test_resume_falls_back_and_replays_lost_segment(tmp_path):
    """run_checkpointed resume with a corrupt newest generation: falls
    back to .prev and re-runs the lost iterations — the result is
    BITWISE the uninterrupted run's."""
    from lux_tpu import faults
    from lux_tpu.convert import uniform_random_edges as ure

    src, dst = ure(100, 700, seed=61)
    g = Graph.from_edges(src, dst, 100)
    eng = pagerank.build_engine(g, num_parts=2)
    p = str(tmp_path / "pr.npz")
    want = eng.unpad(eng.run(eng.init_state(), 10))

    ckpt.run_checkpointed(eng, eng.init_state(), 10, p, segment=3)
    # newest generation (iter 10) corrupt -> resume replays from 9
    faults.bitflip_checkpoint(p)
    got = ckpt.run_checkpointed(eng, eng.init_state(), 10, p,
                                segment=3, resume=True)
    np.testing.assert_array_equal(eng.unpad(got), want)
    assert ckpt.load(p)[1]["iter"] == 10   # re-saved clean

