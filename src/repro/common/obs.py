"""The program's own spans and counters: one recorder, always on, bounded.

``span(name, **attrs)`` times a stretch of host work. Its entry goes to a
fixed-size ring, on ``time.perf_counter_ns()``, and the same stretch is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` whose ``t_host_ns``
argument is the entry's start: one such annotation in a profiler trace maps
the ring onto the trace's clock. The profiler decides whether annotations
are collected; the ring always is. ``spans`` hands entries out as
``(name, t0, t1, attrs)`` in ``time.perf_counter()`` seconds, the clock of
the callers' own windows.

``record(name, **counts)`` appends one time-stamped counter record (ints
and floats). ``spans(t0, t1)`` and ``records(name, t0, t1)`` return what
lies in a window of ``perf_counter`` time; ``totals()`` and ``summary()``
are the readout over the whole process (count, total and max of every span
in ms, the sum of every counter), kept apart from the ring so that they
stay whole when the ring wraps.

Every backend compile (and persistent-cache load) JAX reports is a
``compile`` record with its ``seconds``.

Nothing here holds a device array.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Iterable, List, Optional

import jax

RING = 65536
TRACE_PREFIX = "repro."
COMPILE = "compile"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Span:
    __slots__ = ("rec", "name", "attrs", "t0", "ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        self.ann = jax.profiler.TraceAnnotation(
            TRACE_PREFIX + self.name, t_host_ns=self.t0, **self.attrs)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        rec, name, t0 = self.rec, self.name, self.t0
        with rec._lock:
            rec._spans.append((name, t0, t1, self.attrs))
            tot = rec._span_totals.get(name)
            if tot is None:
                tot = rec._span_totals[name] = [0, 0, 0]
            dt = t1 - t0
            tot[0] += 1
            tot[1] += dt
            if dt > tot[2]:
                tot[2] = dt
        return False


class Recorder:
    """Spans and counter records in two rings of ``size`` entries, and
    their running totals."""

    def __init__(self, size: int = RING):
        self._spans: collections.deque = collections.deque(maxlen=size)
        self._records: collections.deque = collections.deque(maxlen=size)
        # spans in perf_counter nanoseconds; totals [count, sum ns, max ns]
        self._span_totals: Dict[str, list] = {}
        self._counter_totals: Dict[str, Dict[str, float]] = {}
        self._lock = threading.Lock()

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def record(self, name: str, t: Optional[float] = None, **counts):
        t = time.perf_counter() if t is None else float(t)
        with self._lock:
            self._records.append((name, t, counts))
            tot = self._counter_totals.setdefault(name, {"records": 0})
            tot["records"] += 1
            for k, v in counts.items():
                tot[k] = tot.get(k, 0) + v

    def spans(self, t0: float = float("-inf"), t1: float = float("inf"),
              names: Optional[Iterable[str]] = None) -> List[tuple]:
        """The ring's spans that overlap ``[t0, t1)``, in the order they
        closed: ``(name, t0, t1, attrs)``, unclipped."""
        names = None if names is None else frozenset(names)
        with self._lock:
            ring = list(self._spans)
        out = []
        for name, a, b, attrs in ring:
            a, b = a / 1e9, b / 1e9
            if b > t0 and a < t1 and (names is None or name in names):
                out.append((name, a, b, attrs))
        return out

    def records(self, name: str, t0: float = float("-inf"),
                t1: float = float("inf")) -> List[dict]:
        """The counts of the ring's ``name`` records stamped in
        ``[t0, t1)``, oldest first."""
        with self._lock:
            ring = list(self._records)
        return [c for n, t, c in ring if n == name and t0 <= t < t1]

    def totals(self) -> dict:
        """Over the process: per span ``count``, ``total_ms``, ``max_ms``;
        per counter its ``records`` and the sum of each count."""
        with self._lock:
            return {
                "spans": {n: {"count": c, "total_ms": s / 1e6,
                              "max_ms": m / 1e6}
                          for n, (c, s, m) in sorted(
                              self._span_totals.items())},
                "counters": {n: dict(c) for n, c in sorted(
                    self._counter_totals.items())}}

    def summary(self) -> str:
        """``totals()`` as a table, one line per span and per counter."""
        tot = self.totals()
        lines = [f"{'span':<16} {'count':>8} {'total ms':>12} {'max ms':>10}"]
        for n, s in tot["spans"].items():
            lines.append(f"{n:<16} {s['count']:>8} {s['total_ms']:>12.3f} "
                         f"{s['max_ms']:>10.3f}")
        lines.append(f"{'counter':<16} totals")
        for n, c in tot["counters"].items():
            lines.append(f"{n:<16} " + " ".join(
                f"{k}={v:.6g}" for k, v in c.items()))
        return "\n".join(lines)

    def on_duration(self, event: str, duration: float, **_):
        """A ``jax.monitoring`` duration listener: compiles become
        ``compile`` records."""
        if event == COMPILE_EVENT:
            self.record(COMPILE, seconds=float(duration))


RECORDER = Recorder()
span = RECORDER.span
record = RECORDER.record
spans = RECORDER.spans
records = RECORDER.records
totals = RECORDER.totals
summary = RECORDER.summary
jax.monitoring.register_event_duration_secs_listener(RECORDER.on_duration)
