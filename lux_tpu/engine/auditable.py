"""Shared static-audit surface for the engines (lux_tpu/audit.py).

Both engines register every compiled loop variant as
``(jitted fn, example-args thunk)`` so the auditor can trace the
EXACT programs the engine runs (reference analogue: the compile-time
template contract of core/graph.h:146-225, here checked post-trace
instead of pre-compile).  The thunks build abstract
``ShapeDtypeStruct`` stand-ins where possible; the one materialized
host init they require is stashed in ``_pending_init`` for the next
``init_state`` call, so an audited-then-run engine pays for exactly
one init.
"""

from __future__ import annotations


class AuditableEngine:
    """Mixin: compiled-variant registry + lazy-variant forcing, plus
    the shared PLACEMENT surface (round 11): both engines place state
    with the same (sg, mesh, exchange) triple, and the elastic
    recovery path (lux_tpu/resilience.py, checkpoint.py) reasons
    about placement through ``ndev`` / ``placement_meta`` instead of
    poking at engine internals.

    Subclasses set ``_AUDIT_LAZY`` (attribute names whose
    cached_property builders register variants) and populate
    ``self._audit_variants = {}`` before building programs.
    """

    _AUDIT_LAZY: tuple = ()

    # what the engine's delivery (engine/delivery.py) resolved and
    # built, readable on the engine: audit, comms, memwatch, observe,
    # the CLI and the tests read these names here
    exchange = property(lambda self: self.delivery.exchange)
    gather = property(lambda self: self.delivery.gather)
    pairs = property(lambda self: self.delivery.pairs)
    owner = property(lambda self: self.delivery.owner)
    tiles = property(lambda self: self.delivery.tiles)
    page_plan = property(lambda self: self.delivery.page_plan)
    use_mxu = property(lambda self: self.delivery.use_mxu)
    reduce_method = property(lambda self: self.delivery.reduce_method)
    pair_stream = property(lambda self: self.delivery.pair_stream)
    pair_dot_stream = property(
        lambda self: self.delivery.pair_dot_stream)
    stream_chunks = property(lambda self: self.delivery.stream_chunks)
    owner_minmax_fused = property(
        lambda self: self.delivery.owner_minmax_fused)

    @property
    def ndev(self) -> int:
        """Devices this engine's state is placed over (1 = no mesh)."""
        mesh = getattr(self, "mesh", None)
        return 1 if mesh is None else int(mesh.devices.size)

    def placement_meta(self) -> dict:
        """The placement/config fingerprint checkpoints record
        (checkpoint.py): a resume validates num_parts/vpad/exchange
        (P and the padded layout are FIXED across a recovery; a
        different exchange mode is a different float-reduction order,
        so silently resuming across one would break bitwise
        reproducibility), while an ``ndev`` difference is the
        RE-PLACEMENT contract — the global host view re-shards onto
        any mesh whose size divides num_parts."""
        sg = self.sg
        return {"ndev": self.ndev,
                "num_parts": int(sg.num_parts),
                "vpad": int(sg.vpad),
                "exchange": self.exchange}

    def _register_variant(self, name, jitted, args_thunk):
        """Expose one compiled loop variant to the static program
        auditor: the jitted callable plus a thunk building example
        (abstract where possible) arguments for ``jitted.trace`` —
        the auditor only traces, it never executes or compiles."""
        self._audit_variants[name] = (jitted, args_thunk)

    def audit_programs(self):
        """name -> (jitted, example-args thunk) for every program
        variant this engine can run, the lazily compiled ones forced
        (built, not compiled)."""
        for attr in self._AUDIT_LAZY:
            getattr(self, attr)
        return dict(self._audit_variants)

    def audit_variant(self, name: str):
        """One registered variant WITHOUT forcing the lazy builds —
        the comm observatory's entry (lux_tpu/comms.py traces only
        the per-iteration "step" program, which both engines register
        eagerly at build time)."""
        try:
            return self._audit_variants[name]
        except KeyError:
            raise KeyError(
                f"no registered program variant {name!r} "
                f"(have {sorted(self._audit_variants)}; lazy "
                f"variants appear after audit_programs())") from None

    def comm_ledger(self, check: bool = True):
        """This engine's per-iteration communication ledger
        (lux_tpu/comms.ledger_for): every collective of the "step"
        program priced in wire bytes and cross-checked against the
        NumPy message-count oracle.  Tracing only — no compile, no
        execution."""
        from lux_tpu import comms
        return comms.ledger_for(self, check=check)

    def _consume_pending_init(self):
        """The audit's init probe, if one is stashed (see
        ``_audit_state_sds`` in each engine) — consumed at most once.
        Program inits in this repo are pure functions of sg, so the
        stashed first init IS the init."""
        pending = getattr(self, "_pending_init", None)
        self._pending_init = None
        return pending

    def _drop_pending_init(self):
        """Release the stash without consuming it — called by
        ``place()`` (the checkpoint-resume path): a caller placing
        external state will never need the probe, and holding a full
        padded host init for the engine's lifetime is GBs at scale."""
        self._pending_init = None
