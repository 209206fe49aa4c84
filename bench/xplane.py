"""Profiler trace -> device op intervals, program executions and the
harness's own spans, and from those the per-layer metrics of a cell.

The JAX profiler writes one ``.xplane.pb``.  Each TPU is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation and whose line ``XLA Modules`` holds one event per execution
of a compiled program.  The harness's ``jax.profiler.TraceAnnotation``
spans (named ``bench.*``) sit on the host plane; the profiler puts the
device's events on the host's clock to within about a millisecond and a
half (the recorded test trace), far below the ticks and steps measured.

Every per-layer metric is a file ``bench/metrics/<name>.py`` whose
``reduce(trace, facts)`` returns the metric's value, or ``None`` where
the trace holds nothing for it to read (the metric is then left out).
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


@dataclass
class Device:
    index: int
    ops: List[Interval]
    modules: List[Interval]


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Interval]
    window: Tuple[int, int]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def _events(line) -> List[Interval]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append((e.name, start, start + int(e.duration_ns)))
    out.sort(key=lambda iv: iv[1])
    return out


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``.  The window is the ``bench.traced`` span,
    or, where the harness did not open one, the extent of the device
    operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(Device(
                index=int(m.group(1)),
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                modules=(_events(lines[MODULES_LINE])
                         if MODULES_LINE in lines else [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [iv for iv in _events(line)
                          if iv[0].startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.index)
    spans.sort(key=lambda iv: iv[1])
    outer = [iv for iv in spans if iv[0] == WINDOW_SPAN]
    if outer:
        window = (outer[0][1], outer[0][2])
    else:
        ops = [iv for d in devices for iv in d.ops]
        window = (min(iv[1] for iv in ops), max(iv[2] for iv in ops))
    return Trace(devices=devices, spans=spans, window=window)


# ---------------------------------------------------------------- sets
def union(intervals: Sequence[Interval], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged ``(start, end)`` covering the intervals, clipped to
    ``[lo, hi]``."""
    out: List[List[int]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals: Sequence[Interval], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def minus(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
          ) -> int:
    """Length of the union ``a`` less what the union ``b`` covers."""
    total, j = 0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if e > t:
            total += e - t
    return total


def self_times(ops: Sequence[Interval]) -> List[Tuple[str, int]]:
    """Each operation's time less what the operations nested inside it
    (a loop's body, say) cover."""
    out: List[Tuple[str, int]] = []
    stack: List[list] = []              # [name, start, end, children]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            name, s, e, kids = stack.pop()
            out.append((name, e - s - kids))

    for name, s, e in sorted(ops, key=lambda iv: (iv[1], -iv[2])):
        close(s)
        if stack and e <= stack[-1][2]:
            stack[-1][3] += e - s
        else:
            close(e)
        stack.append([name, s, e, 0])
    close(max((iv[2] for iv in ops), default=0) + 1)
    return out


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """The operations with no other operation nested inside them."""
    srt = sorted(ops, key=lambda iv: (iv[1], -iv[2]))
    return [a for a, b in zip(srt, srt[1:] + [("", 1 << 62, 1 << 62)])
            if not (b[1] < a[2] and b[2] <= a[2])]


def busy_ns(tr: Trace, dev: Device) -> int:
    return covered(dev.ops, *tr.window)


def span_at(tr: Trace, t: int) -> str:
    """The innermost harness span open at ``t`` (``host`` where none)."""
    best = None
    for name, s, e in tr.spans:
        if name == WINDOW_SPAN or not s <= t < e:
            continue
        if best is None or s >= best[1]:
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "host"


def in_window(tr: Trace, ivs: Sequence[Interval]) -> List[Interval]:
    lo, hi = tr.window
    return [iv for iv in ivs if iv[1] >= lo and iv[2] <= hi]


def main_program(tr: Trace, dev: Device) -> Optional[str]:
    """The program (module name without its id) that took the most
    device time in the window: the serve step or the train step."""
    tot: Dict[str, int] = {}
    for name, s, e in in_window(tr, dev.modules):
        key = name.split("(")[0]
        tot[key] = tot.get(key, 0) + e - s
    return max(tot, key=tot.get) if tot else None


def executions(tr: Trace, dev: Device, program: Optional[str] = None
               ) -> List[Interval]:
    """Executions of ``program`` (default: :func:`main_program`) that lie
    wholly inside the window, in time order."""
    program = program or main_program(tr, dev)
    return [iv for iv in in_window(tr, dev.modules)
            if iv[0].split("(")[0] == program]


# ---------------------------------------------------------------- cell
def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time (their own time, less
    nested operations; seconds per chip, averaged over the chips; named
    by the first 100 characters of the HLO instruction), and the longest
    idle stretches of the first chip named by the harness span open at
    their middle."""
    n = max(len(tr.devices), 1)
    per_op: Dict[str, float] = {}
    for dev in tr.devices:
        for name, t in self_times(in_window(tr, dev.ops)):
            key = name[:100]
            per_op[key] = per_op.get(key, 0.0) + t / 1e9 / n
    lo, hi = tr.window
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    if tr.devices:
        idle = sorted(((span_at(tr, (s + e) // 2), (e - s) / 1e9)
                       for s, e in gaps(tr.devices[0].ops, lo, hi)),
                      key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def reduce_cell(cell, path: str, facts: dict, log
                ) -> Tuple[dict, Tuple[float, float], dict]:
    """Per-layer metrics of ``cell`` from the trace at ``path``; returns
    (metrics, (busy_s, window_s), breakdown)."""
    from harness import BENCH, load_module
    tr = load(path)
    if not tr.devices:
        raise RuntimeError(f"no TPU plane in {path}")
    window_s = tr.window_ns / 1e9
    busy_s = sum(busy_ns(tr, d) for d in tr.devices) / len(tr.devices) / 1e9
    log(f"trace: {os.path.getsize(path)} bytes, {len(tr.devices)} "
        f"device(s), {sum(len(d.ops) for d in tr.devices)} ops, "
        f"{len(tr.spans)} spans, window_s={window_s!r} busy_s={busy_s!r}")
    metrics = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.reduce(tr, facts)
        if value is None:
            log(f"metric {m['name']}: nothing to read in the trace")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        log(f"metric {m['name']}={float(value)!r} {m['unit']}")
    return metrics, (busy_s, window_s), breakdown(tr)
