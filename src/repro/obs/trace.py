"""Span/counter recorder with two sinks: Chrome-trace (Perfetto-loadable)
JSON export, and the JAX profiler's own trace.

One process-global :class:`Tracer` collects *complete* span events
(``ph: "X"``: name, timestamp, duration, process, thread) and counter
samples (``ph: "C"``), and serializes them to the Chrome trace-event
JSON format that ``ui.perfetto.dev`` / ``chrome://tracing`` load
directly.  That sink is switched on with :func:`enable`.

The second sink needs no switch: while a JAX profiler session runs
(``jax.profiler.trace(dir)``, ``start_trace``), every span is also a
``jax.profiler.TraceAnnotation`` with its args as the event's stats, a
counter becomes an arg of the innermost open span of its thread, and
an instant a zero-length event.  They land on the profile's host plane,
on the same clock as the device's operations.

Design constraints, in order:

1. **Disabled means free.**  With neither sink on, the module-level
   :func:`span` returns a shared no-op context manager without
   allocating, so instrumentation sites sprinkled through hot dispatch
   paths cost one attribute check and one profiler-session check (<2%
   on the executor bench, gated by the benchmark's
   ``trace_off_overhead`` figure).  Callers whose span args cost work
   compute them only when the span is live (``sp is not _NULL_SPAN``).
   Importing this module imports no jax: the profiler's session check
   is looked up once ``jax`` is imported, and cached.
2. **Thread-safe nesting.**  Spans nest per thread (each thread has its
   own open-span stack); the event list append is lock-protected, so
   worker threads (async checkpointer, data prefetch) can trace freely.
3. **Self-describing export.**  ``export()`` emits process/thread
   metadata records and keeps every span's ``args`` (schedule kind, r,
   n_buckets, bytes, ...), so a trace is readable without the code.

>>> t = Tracer(enabled=True)
>>> with t.span("tick", cat="exec", step=3):
...     with t.span("combine", cat="exec"):
...         pass
>>> t.counter("bytes_tx", 4096)
>>> ev = t.export()["traceEvents"]
>>> [e["ph"] for e in ev if e["ph"] != "M"]
['X', 'X', 'C']
>>> sorted(e["name"] for e in ev if e["ph"] == "X")
['combine', 'tick']
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


# ---------------------------------------------------------------------------
#  the profiler sink
# ---------------------------------------------------------------------------

_annotation: Optional[Callable] = None    # jax.profiler.TraceAnnotation
_session: Optional[Callable[[], bool]] = None   # its is_enabled
_open = threading.local()     # per thread: the open spans that have an
#                               annotation, innermost last


def profiling() -> bool:
    """True while a JAX profiler session runs in this process.  Always
    False until ``jax`` has been imported (by anyone); the check is
    then resolved once and cached."""
    global _annotation, _session
    if _session is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _annotation, _session = TraceAnnotation, TraceAnnotation.is_enabled
    return _session()


def _stat(v):
    """A profiler stat value: numbers and strings as they are, anything
    else as its text."""
    return v if isinstance(v, (str, int, float)) else str(v)


def _open_spans() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


class _Span:
    """One open span: appended to the Chrome tracer's event list on exit
    (``tracer`` None: that sink is off), and, with ``annotate``, open as
    a profiler annotation meanwhile."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotate",
                 "_ann")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: dict, annotate: bool):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._annotate = annotate
        self._ann = None

    def set(self, **args) -> "_Span":
        """Attach result metadata discovered while the span is open."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**{k: _stat(v) for k, v in args.items()})
        return self

    def __enter__(self):
        if self._annotate:
            self._ann = _annotation(
                self.name, **{k: _stat(v) for k, v in self.args.items()})
            self._ann.__enter__()
            _open_spans().append(self)
        if self._tracer is not None:
            self._t0 = self._tracer._now_us()
            self._tracer._push(self)
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            t1 = self._tracer._now_us()
            self._tracer._pop(self, self._t0, t1 - self._t0)
        if self._ann is not None:
            st = _open_spans()
            if st and st[-1] is self:
                st.pop()
            self._ann.__exit__(None, None, None)
            self._ann = None
        return False


class Tracer:
    """Span/counter recorder; see module docstring.

    ``enabled`` may be flipped at runtime; events recorded while
    disabled are simply not recorded (open spans straddling the flip
    close without emitting).
    """

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tids: Dict[int, int] = {}
        self._t0_ns = time.perf_counter_ns()
        self._pid = os.getpid()

    # ------------------------------------------------------------ clock
    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # ------------------------------------------------------------ spans
    def span(self, name: str, cat: str = "", **args):
        """Context manager recording one complete ("X") event, and a
        profiler annotation while a profiler session runs."""
        annotate = profiling()
        if not self.enabled and not annotate:
            return _NULL_SPAN
        return _Span(self if self.enabled else None, name, cat, args,
                     annotate)

    def _push(self, sp: _Span) -> None:
        self._stack().append(sp)

    def _pop(self, sp: _Span, ts: float, dur: float) -> None:
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        if not self.enabled:
            return
        ev = {"name": sp.name, "cat": sp.cat or "span", "ph": "X",
              "ts": round(ts, 3), "dur": round(max(dur, 0.0), 3),
              "pid": self._pid, "tid": self._tid()}
        if sp.args:
            ev["args"] = _jsonable(sp.args)
        with self._lock:
            self._events.append(ev)

    @property
    def depth(self) -> int:
        """Open-span nesting depth of the calling thread."""
        return len(self._stack())

    # --------------------------------------------------------- counters
    def counter(self, name: str, value, cat: str = "counter") -> None:
        """Record one counter sample (Chrome ``"C"`` event); under a
        profiler session, also an arg of the innermost open span."""
        if profiling():
            st = _open_spans()
            if st and st[-1]._ann is not None:
                st[-1]._ann.set_metadata(**{name: _stat(value)})
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "C",
              "ts": round(self._now_us(), 3), "pid": self._pid,
              "tid": self._tid(), "args": {name: value}}
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, cat: str = "mark", **args) -> None:
        """Record one instant ("i") event (a point-in-time mark); under
        a profiler session, also a zero-length annotation."""
        if profiling():
            with _annotation(name, **{k: _stat(v) for k, v in args.items()}):
                pass
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": round(self._now_us(), 3), "pid": self._pid,
              "tid": self._tid()}
        if args:
            ev["args"] = _jsonable(args)
        with self._lock:
            self._events.append(ev)

    # ----------------------------------------------------------- export
    def export(self, process_name: str = "repro") -> dict:
        """Chrome trace-event JSON payload (Perfetto-loadable)."""
        with self._lock:
            events = list(self._events)
            tids = dict(self._tids)
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": process_name}}]
        for ident, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": f"thread-{tid}"}})
        events.sort(key=lambda e: e.get("ts", 0.0))
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def save(self, path: str, process_name: str = "repro") -> str:
        """Write the exported trace JSON to ``path`` (dirs created)."""
        path = os.path.abspath(path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.export(process_name), f)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    @property
    def n_events(self) -> int:
        with self._lock:
            return len(self._events)


def _jsonable(args: dict) -> dict:
    out = {}
    for k, v in args.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = [x if isinstance(x, (str, int, float, bool))
                      else str(x) for x in v]
        else:
            out[k] = str(v)
    return out


# ---------------------------------------------------------------------------
#  process-global tracer
# ---------------------------------------------------------------------------

_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the global tracer (tests); returns the previous one."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def enable(clear: bool = False) -> Tracer:
    """Turn the global tracer on (optionally dropping recorded events)."""
    if clear:
        _tracer.clear()
    _tracer.enabled = True
    return _tracer


def disable() -> Tracer:
    _tracer.enabled = False
    return _tracer


def span(name: str, cat: str = "", **args):
    """Module-level span against the global tracer.

    With neither sink on, the fast path returns a shared no-op context
    manager without constructing anything -- safe to call in dispatch
    loops.
    """
    t = _tracer
    if not t.enabled and not profiling():
        return _NULL_SPAN
    return t.span(name, cat, **args)


def counter(name: str, value, cat: str = "counter") -> None:
    """Module-level counter sample against the global tracer."""
    t = _tracer
    if t.enabled or profiling():
        t.counter(name, value, cat)
