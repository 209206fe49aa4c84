"""What the program itself put into a cell's profiler trace: its host
spans with their args, and the named scopes of its device operations.

``xplane.load`` gives the harness's ``bench.*`` spans and device
operations named by their HLO instruction.  The program's reducers need
two things more, read here from the same ``.xplane.pb``:

* ``spans``: the program's host spans (``engine.*``, ``train.*``; see
  ``repro.obs.trace``), each with the args the profiler kept as stats;
* ``scopes``: per compiled program, named as the ``XLA Modules`` line
  names its executions (``jit_step(1234)``), a map from HLO instruction
  name to its ``op_name``, the path of named scopes it was traced under
  (``jit(step)/while/body/attn/dot_general``).  The map comes from the
  HLO that the profiler stores on the ``/host:metadata`` plane, decoded
  from the protobuf wire format with the standard library alone; an
  instruction XLA made without metadata takes a neighbour's name.

A trace is found again by its window: the file under the harness's
trace directory whose ``bench.traced`` span (or, where it has none, the
extent of its device operations) equals the loaded trace's window.  The
result is cached on the trace as ``tr.program``; a test hands a
hand-made trace its :class:`Program` by setting that attribute.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import harness
import xplane

SPAN_PREFIXES = ("engine.", "train.")
HLO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
# how far the profiler may put a device event before the host event that
# caused it (its host and device clocks are aligned to about 1.5 ms)
CLOCK_SLACK_NS = 5_000_000


@dataclass
class Span:
    name: str
    start: int
    end: int
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class Program:
    spans: List[Span] = field(default_factory=list)
    # program name -> HLO instruction name -> op_name
    scopes: Dict[str, Dict[str, str]] = field(default_factory=dict)


# ---------------------------------------------------------------- protobuf
def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized message: an int for a
    varint, a memoryview for anything length-delimited or fixed."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _first(msg, num: int, default=b""):
    return next((v for k, v in _fields(msg) if k == num), default)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _packed(v) -> List[int]:
    """The varints of a packed repeated field (a single unpacked one
    arrives as an int)."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


# instructions that only move values between computations: they neither
# lend their op_name to a neighbour nor take one
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "while", "call",
             "conditional"}


def _inherit(insts) -> None:
    """Give each instruction of one computation that XLA made without
    metadata (a layout copy, a convert it moved) the ``op_name`` of the
    operation it feeds, or else of the one that feeds it, repeated until
    nothing changes.  ``insts``: [id, name, opcode, op_name, operand
    ids], updated in place."""
    by_id = {ins[0]: ins for ins in insts}
    users: Dict[int, list] = {}
    for ins in insts:
        for o in ins[4]:
            users.setdefault(o, []).append(ins)
    changed = True
    while changed:
        changed = False
        for ins in insts:
            if ins[3] or ins[2] in _PLUMBING:
                continue
            near = users.get(ins[0], []) + [by_id[o] for o in ins[4]
                                             if o in by_id]
            donor = next((n for n in near
                          if n[3] and n[2] not in _PLUMBING), None)
            if donor is not None:
                ins[3] = donor[3]
                changed = True


def hlo_op_names(hlo_proto, inherit: bool = True) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` of one serialized ``HloProto``
    (instructions without one map to "").  With ``inherit``, one that
    XLA made without metadata takes the name of a neighbour
    (:func:`_inherit`)."""
    out: Dict[str, str] = {}
    module = _first(hlo_proto, 1)                   # HloProto.hlo_module
    for num, comp in _fields(module):
        if num != 3:                                # .computations
            continue
        insts = []
        for cnum, inst in _fields(comp):
            if cnum != 2:                           # .instructions
                continue
            row = [None, "", "", "", []]
            for inum, v in _fields(inst):
                if inum == 35:                      # .id
                    row[0] = v
                elif inum == 1:                     # .name
                    row[1] = _text(v)
                elif inum == 2:                     # .opcode
                    row[2] = _text(v)
                elif inum == 7:                     # .metadata
                    row[3] = _text(_first(v, 2))    # OpMetadata.op_name
                elif inum == 36:                    # .operand_ids
                    row[4] += _packed(v)
            insts.append(row)
        if inherit:
            _inherit(insts)
        out.update((row[1], row[3]) for row in insts)
    return out


def read_scopes(raw: bytes, inherit: bool = True
                ) -> Dict[str, Dict[str, str]]:
    """Program name -> instruction -> op_name, from a serialized
    ``XSpace``: the ``Hlo Proto`` stats of the metadata plane's event
    metadata, each named as the program's executions are."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:                                # XSpace.planes
            continue
        if _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_ids = set()
        events = []
        for pnum, v in _fields(plane):
            if pnum == 5:                           # stat_metadata entry
                meta = _first(v, 2)
                if _text(_first(meta, 2)) == HLO_STAT:
                    stat_ids.add(_first(meta, 1, 0))
            elif pnum == 4:                         # event_metadata entry
                events.append(_first(v, 2))
        for ev in events:
            name, protos = "", []
            for enum, v in _fields(ev):
                if enum == 2:                       # XEventMetadata.name
                    name = _text(v)
                elif enum == 5:                     # .stats
                    st = dict(_fields(v))
                    if st.get(1) in stat_ids and 6 in st:   # bytes_value
                        protos.append(st[6])
            for p in protos:
                out.setdefault(name, {}).update(hlo_op_names(p, inherit))
    return out


# ---------------------------------------------------------------- loading
def read_spans(path: str) -> List[Span]:
    """The program's host spans of the trace at ``path``, in start
    order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    start = int(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + int(e.duration_ns),
                                    dict(e.stats)))
    out.sort(key=lambda sp: sp.start)
    return out


def load(path: str) -> Program:
    with open(path, "rb") as f:
        raw = f.read()
    return Program(spans=read_spans(path), scopes=read_scopes(raw))


def _trace_file(tr) -> str:
    """The ``.xplane.pb`` under the harness's trace directory that
    ``tr`` was loaded from, newest first."""
    found = glob.glob(os.path.join(harness.TRACE_DIR, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        if xplane.load(path).window == tr.window:
            return path
    raise RuntimeError(f"no trace under {harness.TRACE_DIR} has the "
                       f"window {tr.window}")


def program(tr) -> Program:
    """What the program put into trace ``tr`` (cached as
    ``tr.program``)."""
    prog = getattr(tr, "program", None)
    if prog is None:
        prog = tr.program = load(_trace_file(tr))
    return prog


# ---------------------------------------------------------------- reading
def spans(tr, *names: str) -> List[Span]:
    """The program's spans of these names that lie inside the window."""
    lo, hi = tr.window
    return [sp for sp in program(tr).spans
            if sp.name in names and lo <= sp.start and sp.end <= hi]


def ticks(tr) -> List[Span]:
    """The window's engine ticks that ran the serve step (those with an
    ``s`` arg; a call that found nothing to run has none)."""
    return [sp for sp in spans(tr, "engine.tick") if "s" in sp.args]


def tick_of(tr, executions) -> List[Span]:
    """The engine tick that dispatched each serve-step execution.

    Each ``engine.dispatch`` makes one execution, and the chip runs them
    in the order they were made.  The window opens on an idle chip, so
    its first execution belongs to the dispatch nearest it in time, and
    each later one to the next dispatch.  Pairing by order keeps the
    pairs right where the profiler's host and device clocks disagree by
    more than the time from a dispatch to the start of its execution.
    Raises where an execution has no dispatch or no tick, or starts
    more than ``CLOCK_SLACK_NS`` before its dispatch: pairs one tick
    off."""
    if not executions:
        return []
    dispatches = spans(tr, "engine.dispatch")
    if not dispatches:
        raise RuntimeError("serve-step executions but no engine.dispatch "
                           "span in the window")
    t0 = executions[0][1]
    first = min(range(len(dispatches)),
                key=lambda j: abs(dispatches[j].start - t0))
    paired = dispatches[first:first + len(executions)]
    if len(paired) < len(executions):
        raise RuntimeError(f"{len(executions)} serve-step executions but "
                           f"{len(paired)} dispatches from the first")
    tks = ticks(tr)
    starts = [t.start for t in tks]
    out = []
    for (_, start, _), d in zip(executions, paired):
        i = bisect.bisect_right(starts, d.start) - 1
        if i < 0 or tks[i].end < d.end:
            raise RuntimeError(f"no engine tick around the dispatch at "
                               f"{d.start} ns")
        if start < d.start - CLOCK_SLACK_NS:
            raise RuntimeError(f"the execution at {start} ns starts before "
                               f"its dispatch at {d.start} ns")
        out.append(tks[i])
    return out


_TOKEN = re.compile(r"[/()]")


def scope_tokens(op_name: str) -> set:
    """The whole names on an ``op_name`` path, wrappers such as
    ``transpose(jvp(...))`` split off."""
    return {t for t in _TOKEN.split(op_name) if t}


def instruction(op: str) -> str:
    """The HLO instruction name of a device operation's event name
    (``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``)."""
    return op.lstrip("%").split(" ", 1)[0]


def _program_scopes(tr, module: str) -> Dict[str, str]:
    scopes = program(tr).scopes
    if module in scopes:
        return scopes[module]
    raise RuntimeError(f"the trace holds no HLO for {module}")


def ops_in(dev, lo: int, hi: int) -> List[xplane.Interval]:
    """The device's operations that lie inside ``[lo, hi]`` (its
    operations are in start order, as ``xplane.load`` gives them)."""
    starts = getattr(dev, "starts", None)
    if starts is None:
        starts = dev.starts = [iv[1] for iv in dev.ops]
    i, j = bisect.bisect_left(starts, lo), bisect.bisect_right(starts, hi)
    return [iv for iv in dev.ops[i:j] if iv[2] <= hi]


def scope_ns(tr, dev, execution, want: str, without: str = "") -> int:
    """Device time (operations' own time, less nested operations) of one
    execution's operations whose ``op_name`` holds the scope ``want``
    and not the scope ``without``."""
    module, lo, hi = execution
    names = _program_scopes(tr, module)
    total = 0
    for op, t in xplane.self_times(ops_in(dev, lo, hi)):
        tokens = scope_tokens(names.get(instruction(op), ""))
        if want in tokens and (not without or without not in tokens):
            total += t
    return total


def scope_ms(tr, executions, want: str, without: str = ""
             ) -> Optional[float]:
    """Mean device time per execution under scope ``want`` (and not
    ``without``); None where no operation of the executions is under
    it."""
    dev = tr.devices[0]
    ns = [scope_ns(tr, dev, ex, want, without) for ex in executions]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / 1e6


def kind_ms(tr, kind: str, want: str, without: str = ""
            ) -> Optional[float]:
    """:func:`scope_ms` over the serve-step executions of one tick kind
    (``decode``: S = 1, ``chunk``: S = prefill_chunk), the kind read
    from the ``s`` arg of the tick that dispatched each."""
    if not ticks(tr):
        return None
    ex = xplane.executions(tr, tr.devices[0])
    sel = [e for e, t in zip(ex, tick_of(tr, ex))
           if (t.args["s"] == 1) == (kind == "decode")]
    return scope_ms(tr, sel, want, without) if sel else None


def idle_in_ms(tr, *names: str) -> Optional[float]:
    """Mean over consecutive serve-step executions of the device-idle
    time between them (as ``host_gap_ms.serve`` counts it) that lies
    inside the program spans ``names``."""
    cover = xplane.union([(sp.name, sp.start, sp.end)
                          for sp in spans(tr, *names)], *tr.window)
    if not cover:
        return None
    dev = tr.devices[0]
    ex = xplane.executions(tr, dev)
    if len(ex) < 2:
        return None
    inside = 0
    for a, b in zip(ex, ex[1:]):
        idle = xplane.gaps(dev.ops, a[2], b[1])
        inside += sum(e - s for s, e in idle) - xplane.minus(idle, cover)
    return inside / (len(ex) - 1) / 1e6
