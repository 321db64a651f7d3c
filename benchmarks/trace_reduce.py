"""From a profiler trace (``*.xplane.pb``) to device busy time, time by
named scope, collective time and the longest idle gaps.

The file is an ``XSpace`` protocol buffer.  It is read here with a
small wire-format reader and nothing else, because ``jax.profiler.
ProfileData`` does not hand out what the reduction needs: on this
installation the scope an XLA op belongs to is not in the event's name
(the name is the op's HLO text) but in the ``tf_op`` stat of the
event's METADATA, e.g. ``jit(run)/while/body/closed_call/lux_pagerank/
vmap(lux_reduce)/...`` (looked at by hand, PR 23).

What the device plane holds (``/device:TPU:<n>``):

- line ``XLA Modules``: one event per executed program;
- line ``XLA Ops``: one event per executed op, NESTED (a ``while`` or
  ``cond`` op spans its body's ops), so time is attributed by SELF
  time: an op's duration minus that of the ops inside it;
- line ``Async XLA Ops``: copy-start/done pairs etc., overlapping the
  ops above; not counted as busy time of their own.

Host spans written by ``jax.profiler.TraceAnnotation`` are on the
``/host:CPU`` plane, on the same clock.

A fused op carries one ``tf_op`` (its root's).  Where the HLO module
is in the trace (plane ``/host:metadata``, stat ``Hlo Proto``), the
scopes of every instruction inside the fusion are read too, and a
fusion whose instructions lie in two different scopes is attributed
to the scope ``mixed`` (never dropped).
"""

from __future__ import annotations

import dataclasses
import re

SCOPE_RE = re.compile(r"lux_[A-Za-z0-9_]+")
COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?\b")
HOST_SPAN_PREFIX = "bench:"


# ---- protocol-buffer wire format -----------------------------------

def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """Yield (field number, wire type, value) of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


# ---- XSpace ---------------------------------------------------------

@dataclasses.dataclass
class Event:
    name: str            # metadata name (HLO text for XLA ops)
    display: str
    start_ps: int        # on the trace's common clock
    duration_ps: int
    tf_op: str = ""

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> [Event] sorted by start
    hlo_protos: dict     # program name -> bytes (metadata plane only)


def _parse_stat_metadata(entry):
    for f, _w, v in _fields(entry):
        if f == 2:
            sid = name = None
            for f2, _w2, v2 in _fields(v):
                if f2 == 1:
                    sid = v2
                elif f2 == 2:
                    name = _text(v2)
            return sid, name
    return None, None


def _parse_event_metadata(entry, stat_names):
    """-> (id, dict(name, display, tf_op, hlo))"""
    for f, _w, v in _fields(entry):
        if f != 2:
            continue
        out = {"name": "", "display": "", "tf_op": "", "hlo": None}
        mid = None
        for f2, _w2, v2 in _fields(v):
            if f2 == 1:
                mid = v2
            elif f2 == 2:
                out["name"] = _text(v2)
            elif f2 == 4:
                out["display"] = _text(v2)
            elif f2 == 5:
                sname = val = None
                for f3, w3, v3 in _fields(v2):
                    if f3 == 1:
                        sname = stat_names.get(v3)
                    elif w3 == 2:
                        val = v3
                if sname == "tf_op" and val is not None:
                    out["tf_op"] = _text(val)
                elif sname == "Hlo Proto" and val is not None:
                    out["hlo"] = bytes(val)
        return mid, out
    return None, None


def parse_xspace(data) -> list:
    """``data``: the bytes of an ``.xplane.pb`` file -> [Plane]."""
    planes = []
    for f, _w, plane_buf in _fields(memoryview(data)):
        if f != 1:
            continue
        name, line_bufs, em_bufs, stat_names = "", [], [], {}
        for f2, _w2, v2 in _fields(plane_buf):
            if f2 == 2:
                name = _text(v2)
            elif f2 == 3:
                line_bufs.append(v2)
            elif f2 == 4:
                em_bufs.append(v2)
            elif f2 == 5:
                sid, sname = _parse_stat_metadata(v2)
                if sid is not None:
                    stat_names[sid] = sname
        meta, protos = {}, {}
        for buf in em_bufs:
            mid, m = _parse_event_metadata(buf, stat_names)
            if mid is None:
                continue
            meta[mid] = m
            if m["hlo"] is not None:
                protos[m["name"]] = m["hlo"]
        lines = {}
        for buf in line_bufs:
            lname, t0_ns, ev_bufs = "", 0, []
            for f3, _w3, v3 in _fields(buf):
                if f3 == 2:
                    lname = _text(v3)
                elif f3 == 3:
                    t0_ns = _signed(v3)
                elif f3 == 4:
                    ev_bufs.append(v3)
            events = []
            for eb in ev_bufs:
                mid = off = dur = 0
                for f4, _w4, v4 in _fields(eb):
                    if f4 == 1:
                        mid = v4
                    elif f4 == 2:
                        off = _signed(v4)
                    elif f4 == 3:
                        dur = _signed(v4)
                m = meta.get(mid, {})
                events.append(Event(
                    name=m.get("name", ""), display=m.get("display", ""),
                    start_ps=t0_ns * 1000 + off, duration_ps=dur,
                    tf_op=m.get("tf_op", "")))
            events.sort(key=lambda e: (e.start_ps, -e.duration_ps))
            lines.setdefault(lname, []).extend(events)
        planes.append(Plane(name=name, lines=lines, hlo_protos=protos))
    return planes


# ---- HLO module: which scopes does a fusion's body touch ------------

def fusion_scopes(hlo_proto: bytes) -> dict:
    """instruction name -> set of scope chains of the instructions in
    the computations it calls (fusions, mostly).  ``HloProto`` field
    numbers: hlo_module=1; module.computations=3; computation.id=5,
    .instructions=2; instruction.name=1, .metadata=7 (op_name=2),
    .called_computation_ids=38."""
    comps = {}          # computation id -> [(name, op_name, called)]
    for f, _w, module in _fields(memoryview(hlo_proto)):
        if f != 1:
            continue
        for f2, _w2, comp in _fields(module):
            if f2 != 3:
                continue
            cid, instrs = None, []
            for f3, w3, v3 in _fields(comp):
                if f3 == 5 and w3 == 0:
                    cid = v3
                elif f3 == 2:
                    iname, op_name, called = "", "", []
                    for f4, w4, v4 in _fields(v3):
                        if f4 == 1:
                            iname = _text(v4)
                        elif f4 == 7:
                            for f5, _w5, v5 in _fields(v4):
                                if f5 == 2:
                                    op_name = _text(v5)
                        elif f4 == 38:
                            if w4 == 0:
                                called.append(v4)
                            else:       # packed
                                j = 0
                                while j < len(v4):
                                    c, j = _varint(v4, j)
                                    called.append(c)
                    instrs.append((iname, op_name, called))
            if cid is not None:
                comps[cid] = instrs
    out = {}
    for instrs in comps.values():
        for iname, _op, called in instrs:
            chains = set()
            for cid in called:
                for _n, op_name, _c in comps.get(cid, ()):
                    chain = scope_chain(op_name)
                    if chain:
                        chains.add(chain)
            if chains:
                out[iname] = chains
    return out


def scope_chain(tf_op: str) -> str:
    """``jit(run)/while/body/lux_pagerank/vmap(lux_reduce)/add`` ->
    ``lux_pagerank/lux_reduce``: the named scopes on the op's path,
    outermost first."""
    return "/".join(SCOPE_RE.findall(tf_op))


def attribute(own: str, inner) -> str:
    """The scope an op's time goes to: its own chain; ``mixed`` when
    the instructions inside it lie in two chains of which neither
    contains the other; ``unscoped`` when no scope is on any path."""
    chains = set(inner)
    if own:
        chains.add(own)
    if not chains:
        return "unscoped"
    maximal = [c for c in chains
               if not any(o.startswith(c + "/") for o in chains)]
    if len(maximal) > 1:
        return "mixed"
    return own or maximal[0]


# ---- reduction ------------------------------------------------------

def _union(intervals):
    """Total length and merged list of half-open (start, end)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(events):
    """[(Event, self picoseconds)] for one line of NESTED events."""
    out, stack = [], []      # stack of [event, child time]
    for ev in events:
        while stack and ev.start_ps >= stack[-1][0].end_ps:
            done, child = stack.pop()
            out.append((done, max(done.duration_ps - child, 0)))
        if stack:
            stack[-1][1] += ev.duration_ps
        stack.append([ev, 0])
    while stack:
        done, child = stack.pop()
        out.append((done, max(done.duration_ps - child, 0)))
    return out


def _op_name(ev: Event) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    if ev.display:
        return ev.display
    head = ev.name.split(" = ", 1)[0]
    return head.lstrip("%")


def _opcode(ev: Event) -> str:
    """The HLO opcode out of the op's text (``... = shape opcode(``)."""
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", ev.name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


@dataclasses.dataclass
class DeviceSummary:
    plane: str
    busy_s: float
    first_ps: int
    last_ps: int
    scope_s: dict            # scope chain (or 'mixed'/'unscoped') -> s
    collective_s: float
    op_s: dict               # "scope :: opcode" -> seconds
    gaps: list               # [(start_ps, end_ps)] between programs


@dataclasses.dataclass
class TraceSummary:
    devices: list            # [DeviceSummary]
    host_spans: list         # [(name, start_ps, end_ps)]

    @property
    def busy_s(self) -> float:
        """Mean over the device planes that ran anything."""
        used = [d.busy_s for d in self.devices if d.busy_s > 0]
        return sum(used) / len(used) if used else 0.0

    def scope_seconds(self, *scopes: str) -> float:
        """Mean over devices of the self time of ops whose chain holds
        one of ``scopes`` as a component (each op counted once)."""
        vals = []
        for d in self.devices:
            if d.busy_s <= 0:
                continue
            vals.append(sum(s for chain, s in d.scope_s.items()
                            if set(scopes) & set(chain.split("/"))))
        return sum(vals) / len(vals) if vals else 0.0

    def collective_seconds(self) -> float:
        vals = [d.collective_s for d in self.devices if d.busy_s > 0]
        return sum(vals) / len(vals) if vals else 0.0

    def top_ops(self, n: int = 10):
        total = {}
        used = [d for d in self.devices if d.busy_s > 0]
        for d in used:
            for k, s in d.op_s.items():
                total[k] = total.get(k, 0.0) + s / len(used)
        return sorted(([k, s] for k, s in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10):
        """The longest idle gaps of the first used device, each named
        by the benchmark's host span that covers its middle."""
        used = [d for d in self.devices if d.busy_s > 0]
        if not used:
            return []
        out = []
        for s, e in sorted(used[0].gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) // 2
            inside = [(he - hs, name) for name, hs, he in self.host_spans
                      if hs <= mid < he]
            label = min(inside)[1] if inside else "outside_any_span"
            out.append([label, (e - s) / 1e12])
        return out


def reduce_planes(planes) -> TraceSummary:
    scopes_by_instr = {}
    for p in planes:
        for proto in p.hlo_protos.values():
            for iname, chains in fusion_scopes(proto).items():
                scopes_by_instr.setdefault(iname, set()).update(chains)
    devices, host_spans = [], []
    for p in planes:
        if p.name.startswith("/host:CPU"):
            for events in p.lines.values():
                for ev in events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.name[len(HOST_SPAN_PREFIX):],
                                           ev.start_ps, ev.end_ps))
            continue
        if not p.name.startswith("/device:TPU:"):
            continue
        ops = p.lines.get("XLA Ops", [])
        modules = p.lines.get("XLA Modules", [])
        busy_ps, merged = _union(
            [(e.start_ps, e.end_ps) for e in ops + modules
             if e.duration_ps > 0])
        scope_s, op_s, coll_ps = {}, {}, 0
        for ev, self_ps in self_times(ops):
            if self_ps <= 0:
                continue
            chain = scope_chain(ev.tf_op)
            chain = attribute(chain,
                              scopes_by_instr.get(_op_name(ev), ()))
            scope_s[chain] = scope_s.get(chain, 0.0) + self_ps / 1e12
            opcode = _opcode(ev)
            if COLLECTIVE_RE.search(opcode) or COLLECTIVE_RE.search(
                    _op_name(ev)):
                coll_ps += self_ps
            key = f"{chain} :: {opcode or _op_name(ev)}"
            op_s[key] = op_s.get(key, 0.0) + self_ps / 1e12
        gaps = [(merged[i][1], merged[i + 1][0])
                for i in range(len(merged) - 1)]
        devices.append(DeviceSummary(
            plane=p.name, busy_s=busy_ps / 1e12,
            first_ps=merged[0][0] if merged else 0,
            last_ps=merged[-1][1] if merged else 0,
            scope_s=scope_s, collective_s=coll_ps / 1e12, op_s=op_s,
            gaps=gaps))
    return TraceSummary(devices=devices, host_spans=host_spans)


def reduce_file(path: str) -> TraceSummary:
    with open(path, "rb") as f:
        return reduce_planes(parse_xspace(f.read()))
