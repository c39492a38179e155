"""Span-based host tracing over the event bus (docs/OBSERVABILITY.md).

*Online* — :class:`TraceContext` times HOST phases (the train loop's
iteration and its parts, construction, checkpoint save, rollback, policy
apply) on ONE clock, ``time.perf_counter_ns``, and keeps one
``(perf_counter_ns, time_ns)`` pair per trajectory to map it to wall time.
A finished span costs two clock reads and a list append on the hot path:
it goes to a bounded in-memory list, is published on the bus when the
owner calls :meth:`TraceContext.drain` (the trainer: at log steps and at
close), and stays reachable in-process through :func:`recorded` after the
bus is closed. Each span also opens the annotation it was given
(``jax.profiler.TraceAnnotation`` from the trainer), so a profile taken
with host tracing on shows the same spans beside the device operations.
The context installs a stamp hook on the bus so every record published
while a span is open carries ``trace_id``/``span_id`` — producers never
change. Nothing here runs inside jit: the device's side is named by
``jax.named_scope`` in the step programs and read from a profiler trace
(telemetry/profiler.py, ``--profile-steps``).

*Offline* — :func:`build_chrome_trace` renders a finished JSONL stream
into Chrome-trace/Perfetto JSON: the host spans on one track, every other
record as an instant on a second.

Everything in this module is pure stdlib: the ``trace`` CLI subcommand
(__main__.py) must run on a machine without jax installed.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
from typing import (Any, Callable, Deque, Dict, Iterable, Iterator, List,
                    Mapping, NamedTuple, Optional, Tuple)

__all__ = [
    "Recording",
    "Span",
    "TraceContext",
    "build_chrome_trace",
    "recorded",
]

# spans a recording keeps for in-process readers, and finished spans a
# context holds before it publishes them without being asked (a run whose
# log interval is longer than this many spans)
KEEP_SPANS = 1 << 16
PENDING_SPANS = 1 << 12
_KEEP_RECORDINGS = 16


def _default_trace_id() -> str:
    # unique enough across runs on one host; injectable for tests
    return f"{os.getpid():x}-{int(time.time() * 1e3):x}"


def _wall_ns(anchors: List[Tuple[int, int]], perf_ns: int) -> int:
    """A ``perf_counter_ns`` reading on the wall clock, through the newest
    ``(perf_ns, wall_ns)`` pair taken at or before it (the first pair for
    what came earlier)."""
    use = anchors[0]
    for a in anchors:
        if a[0] <= perf_ns:
            use = a
    return use[1] + (perf_ns - use[0])


class Span(NamedTuple):
    """A finished host span; both times are ``perf_counter_ns`` readings."""

    name: str
    span_id: str
    parent: Optional[str]
    t0_ns: int
    t1_ns: int
    cat: str
    fields: Dict[str, Any]


class Recording:
    """One run's finished spans, newest ``KEEP_SPANS`` of them, and the
    clock pairs that map them to wall time."""

    def __init__(self, run_id: Optional[str], trace_id: str):
        self.run_id = run_id
        self.trace_id = trace_id
        self.anchors: List[Tuple[int, int]] = []    # (perf_ns, wall_ns)
        self.spans: Deque[Span] = collections.deque(maxlen=KEEP_SPANS)

    def wall_ns(self, perf_ns: int) -> int:
        """``perf_ns`` on the wall clock (``time_ns``)."""
        return _wall_ns(self.anchors, perf_ns)


_RECORDINGS: "collections.OrderedDict[str, Recording]" = \
    collections.OrderedDict()


def recorded(run_id: str) -> Optional[Recording]:
    """The newest recording made under ``run_id`` in this process, also
    after its trainer was closed and freed; None when there is none."""
    return _RECORDINGS.get(run_id)


class TraceContext:
    """Allocates span ids, times spans and publishes ``span`` records.

    Span ids are sequential per-context (``s0001``, ``s0002``, ...) so a
    trace is deterministic given a deterministic schedule; the open-span
    stack is thread-local, so the prefetch thread's io_retry records are
    stamped with ITS innermost span, not the train loop's.

    ``install()`` registers the stamp hook (``trace_id`` always,
    ``span_id`` of the innermost open span when one exists) on the bus;
    without ``install()`` the bus stream is byte-identical to an
    untraced run.

    ``annotate(name)`` and ``step_annotate(name, step_num=...)`` are
    context-manager factories opened with each span (the profiler's
    annotations); both default to none.
    """

    def __init__(self, bus: Any, trace_id: Optional[str] = None,
                 run_id: Optional[str] = None,
                 clock_ns: Callable[[], int] = time.perf_counter_ns,
                 wall_ns: Callable[[], int] = time.time_ns,
                 annotate: Optional[Callable[..., Any]] = None,
                 step_annotate: Optional[Callable[..., Any]] = None):
        self._bus = bus
        self.trace_id = trace_id or _default_trace_id()
        self._clock_ns = clock_ns
        self._wall_ns = wall_ns
        self._annotate = annotate
        self._step_annotate = step_annotate
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._open_names: Dict[str, str] = {}   # B-span id -> name
        self._pending: List[Span] = []
        self.recording = Recording(run_id, self.trace_id)
        self._anchor()
        if run_id is not None:
            _RECORDINGS.pop(run_id, None)
            _RECORDINGS[run_id] = self.recording
            while len(_RECORDINGS) > _KEEP_RECORDINGS:
                _RECORDINGS.popitem(last=False)

    # ------------------------------------------------------------- ids
    def _next_id(self) -> str:
        return f"s{next(self._ids):04x}"

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def current_span(self) -> Optional[str]:
        st = self._stack()
        return st[-1] if st else None

    def _anchor(self) -> Tuple[int, int]:
        pair = (self._clock_ns(), self._wall_ns())
        self.recording.anchors.append(pair)
        return pair

    # ----------------------------------------------------- bus stamping
    def stamp(self) -> Dict[str, Any]:
        """Fields merged (setdefault) onto every published record; called
        by EventBus.publish on the publishing thread — must never
        publish."""
        out: Dict[str, Any] = {"trace_id": self.trace_id}
        cur = self.current_span()
        if cur is not None:
            out["span_id"] = cur
        return out

    def install(self) -> "TraceContext":
        self._bus.set_stamp(self.stamp)
        return self

    def uninstall(self) -> None:
        self._bus.set_stamp(None)

    # ------------------------------------------------------------ spans
    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", step_num: Any = None,
             **fields: Any) -> Iterator[str]:
        """Complete ("X") span around a host phase. With ``step_num`` the
        span is a step of the run: the step annotation is opened instead
        of the plain one, and ``step`` is recorded. Nothing is published
        here: the finished span waits in memory for :meth:`drain`."""
        sid = self._next_id()
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(sid)
        note = None
        if step_num is not None:
            fields["step"] = step_num
            if self._step_annotate is not None:
                note = self._step_annotate(name, step_num=step_num)
        elif self._annotate is not None:
            note = self._annotate(name)
        if note is not None:
            note.__enter__()
        t0 = self._clock_ns()
        try:
            yield sid
        finally:
            t1 = self._clock_ns()
            if note is not None:
                note.__exit__(None, None, None)
            if stack and stack[-1] == sid:
                stack.pop()
            elif sid in stack:       # a trajectory rotated underneath it
                stack.remove(sid)
            done = Span(name, sid, parent, t0, t1, cat, fields)
            self.recording.spans.append(done)
            self._pending.append(done)
            if len(self._pending) >= PENDING_SPANS:
                self.drain()

    def drain(self) -> int:
        """Publish the finished spans that wait in memory, in the order
        they closed (a child before its parent, so readers resolve parents
        at end-of-stream, as events.validate_stream does). Returns how
        many."""
        pending, self._pending = self._pending, []
        for s in pending:
            rec = {"name": s.name, "span_id": s.span_id, "ph": "X",
                   "cat": s.cat, "t0_ns": s.t0_ns,
                   "dur_ns": s.t1_ns - s.t0_ns}
            if s.parent is not None:
                rec["parent_span"] = s.parent
            rec.update(s.fields)
            self._bus.emit("span", **rec)
        return len(pending)

    def begin(self, name: str, cat: str = "host", root: bool = False,
              **fields: Any) -> str:
        """Open a long-lived ("B") span — e.g. a whole trajectory between
        rollbacks. Takes a new clock pair and publishes it (``t0_ns``,
        ``wall_ns``). ``root`` makes it a root of the span tree whatever is
        open (a trajectory rotated from inside an iteration). Must be
        closed with :meth:`end`."""
        sid = self._next_id()
        parent = None if root else self.current_span()
        self._stack().append(sid)
        self._open_names[sid] = name
        perf, wall = self._anchor()
        rec = {"name": name, "span_id": sid, "ph": "B", "cat": cat,
               "t0_ns": perf, "wall_ns": wall}
        if parent is not None:
            rec["parent_span"] = parent
        rec.update(fields)
        self._bus.emit("span", **rec)
        return sid

    def end(self, span_id: str, **fields: Any) -> None:
        name = self._open_names.pop(span_id, "span")
        st = self._stack()
        if span_id in st:
            st.remove(span_id)
        self._bus.emit("span", name=name, span_id=span_id, ph="E",
                       cat="host", t0_ns=self._clock_ns(), **fields)

    def instant(self, name: str, cat: str = "host", **fields: Any) -> str:
        """Zero-duration marker (anomaly pending, preemption signal)."""
        sid = self._next_id()
        parent = self.current_span()
        rec = {"name": name, "span_id": sid, "ph": "i", "cat": cat,
               "t0_ns": self._clock_ns()}
        if parent is not None:
            rec["parent_span"] = parent
        rec.update(fields)
        self._bus.emit("span", **rec)
        return sid


# ---------------------------------------------------------------------
# offline: JSONL -> Chrome-trace JSON
# ---------------------------------------------------------------------

# fixed tid layout, one set per worker (pid). Perfetto shows the thread
# names from the metadata events; numbers keep rows stably ordered.
_TID_HOST = 0
_TID_EVENTS = 1

_TID_NAMES = {
    _TID_HOST: "host phases",
    _TID_EVENTS: "events",
}


def _number(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _span_wall_s(rec: Mapping[str, Any],
                 anchors: List[Tuple[int, int]]) -> Optional[float]:
    """A span record's start on the wall clock, in seconds: its
    ``t0_ns`` through the newest clock pair published at or before it;
    the bus's publish time where the stream has neither."""
    t0 = rec.get("t0_ns")
    if _number(t0) and anchors:
        return _wall_ns(anchors, t0) / 1e9
    ts = rec.get("ts")
    return float(ts) if _number(ts) else None


def _render_span(rec: Mapping[str, Any], t: float,
                 us: Callable[[float], float], pid: int,
                 out: List[Dict[str, Any]]) -> None:
    name = str(rec.get("name", "span"))
    ph = rec.get("ph")
    args = {k: rec[k] for k in ("span_id", "parent_span", "step", "reason",
                                "knob", "path", "ready") if k in rec}
    ev: Dict[str, Any] = {"name": name, "ph": ph, "ts": round(us(t), 1),
                          "pid": pid, "tid": _TID_HOST,
                          "cat": str(rec.get("cat", "host")), "args": args}
    if ph == "X":
        dur = rec.get("dur_ns", 0)
        ev["dur"] = round(max(float(dur), 0.0) / 1e3, 1)
    elif ph == "i":
        ev["s"] = "t"
    elif ph not in ("B", "E"):
        return
    out.append(ev)


def build_chrome_trace(events: Iterable[Mapping[str, Any]],
                       pid: int = 0) -> Dict[str, Any]:
    """Render parsed event records into a Chrome-trace JSON object.

    ``pid`` names the worker: merge several workers' streams into one
    Perfetto view by rendering each with a distinct pid and
    concatenating the ``traceEvents`` lists. Timestamps are µs relative
    to the earliest record, so cross-worker merges stay aligned as long
    as hosts share a clock.
    """
    recs = [r for r in events if isinstance(r, Mapping)]
    anchors = sorted((r["t0_ns"], r["wall_ns"]) for r in recs
                     if r.get("event") == "span"
                     and _number(r.get("t0_ns"))
                     and _number(r.get("wall_ns")))
    timed: List[Tuple[float, Mapping[str, Any]]] = []
    for r in recs:
        if r.get("event") == "span":
            t = _span_wall_s(r, anchors)
        else:
            t = float(r["ts"]) if _number(r.get("ts")) else None
        if t is not None:
            timed.append((t, r))
    base = min((t for t, _ in timed), default=0.0)

    def us(t: float) -> float:
        return (t - base) * 1e6

    out: List[Dict[str, Any]] = []
    for t, r in timed:
        ev = r.get("event")
        if ev == "span":
            _render_span(r, t, us, pid, out)
            continue
        name = str(ev) if isinstance(ev, str) else "<record>"
        args = {k: v for k, v in r.items()
                if isinstance(v, (str, int, float))}
        out.append({"name": name, "ph": "i", "s": "t",
                    "ts": round(us(t), 1), "pid": pid,
                    "tid": _TID_EVENTS, "cat": "event", "args": args})
    meta: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": f"worker {pid}"}},
    ]
    for tid, tname in _TID_NAMES.items():
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": tname}})
        meta.append({"name": "thread_sort_index", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"sort_index": tid}})
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}
