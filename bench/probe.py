"""The benchmark's hooks into the program's experiment.

``Probe`` wraps, from the benchmark's side, the calls the simulator makes
into each layer, and restores them when it closes. A run drives the same
experiment twice, through ``repro.federated.run_algorithm`` both times:

1. the warm pass. The experiment runs through ``warm_updates`` updates,
   past the measured window's expected end, on the same fixed timeline the
   measured pass replays. The cohort step and the batched sketch run for
   real at the first wave of each size and are answered by zeros after
   that; the ingest, the host loop and the evaluation always run. So every
   program the measured pass's waves will use is compiled (or loaded from
   the persistent cache) here: the cohort step and the sketch at each wave
   size, the scanned ingest at each chunk, and the small programs the
   simulator builds eagerly per shape (snapshot gathers, wave slices), at
   a fraction of the measured pass's cost;
2. the measured pass: the same experiment (same seed, same timeline) from
   its start, reusing the engine the warm pass built. Its first updates go
   to the output check (``bench.correct.Record``); after
   ``prefix_updates`` the window opens, and ``WindowClosed`` is raised at
   the ingest that brings the window's updates to ``rate_hint x
   seconds``. Both ends wait for the device; nothing between them does.

The window is a fixed amount of work: since the timeline is the mix's own,
every run's window holds the same waves, which last about ``seconds`` at
the rate the mix states (``rate_hint``, measured on the chip). A window cut
by the clock instead would hold one wave more or less from run to run, and
waves carry unequal numbers of updates in equal device time (in dir0.1, 1
to 4 updates in 0.7 s), so the rate would jump by several percent with the
phase of the deadline.

``PolicyServer.receive_many`` is where updates are counted. With ``spans``
on (the traced run), ``Dispatcher.dispatch_many``, ``cohort_update``,
``receive_many``, the batched sketch and the evaluation are timed on the
host clock and named in the profiler's trace (``TraceAnnotation``), and
every wave's member-steps are counted: its members' real steps, and the
steps its bucketed rows executed, rows times the trip count the program
recorded for the call (``cohort.wave``'s ``schedule``).

Compile events come from JAX's own monitoring: every program built in the
process fires ``/jax/core/compile/backend_compile_duration``, a load from
the persistent compilation cache included.
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.correct import AGGREGATIONS, Record

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the warm pass runs this far past the window's expected end
WARM_MARGIN = 1.5


class WindowClosed(Exception):
    """Raised through the simulator when the measured window has ended."""


class WarmPassDone(Exception):
    """Raised through the simulator when the warm pass has run its course."""


class CompileClock:
    """Backend compiles (and persistent-cache loads) with their time."""

    def __init__(self):
        self.events: List[tuple] = []       # (perf_counter at end, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def between(self, t0: float, t1: float) -> List[float]:
        return [d for t, d in self.events if t0 <= t < t1]

    def close(self) -> None:
        from jax._src import monitoring
        with contextlib.suppress(Exception):
            monitoring._unregister_event_duration_listener_by_callback(
                self._duration)


class Probe:
    def __init__(self, traffic: dict, seconds: float, *, spans: bool = False,
                 on_window_start: Optional[Callable] = None,
                 on_window_end: Optional[Callable] = None):
        self.prefix = int(traffic["prefix_updates"])
        self.window_target = max(1, math.ceil(float(traffic["rate_hint"])
                                              * seconds))
        self.warm_updates = self.prefix + math.ceil(
            WARM_MARGIN * self.window_target)
        self.spans_on = spans
        self.on_window_start = on_window_start
        self.on_window_end = on_window_end
        self.record = Record()
        self.engine = None
        self.dispatcher_result = None
        self.state = "warm"
        self.updates = 0                # ingested in the current pass
        self.window_updates = 0
        self.t_start: Optional[float] = None
        self.t_end: Optional[float] = None
        self.dropped_at_start = 0
        self.versions = [0, 0]          # server version at the window's ends
        self.spans: List[tuple] = []    # (name, t0, t1) on perf_counter
        self.waves: List[dict] = []     # per measured cohort_update
        self.sketch_calls: List[tuple] = []  # (perf_counter, rows)
        self._seen_waves = set()
        self._seen_sketches = set()
        self._undo: List[Callable] = []

    # -- installation -------------------------------------------------------

    def _patch(self, owner, name: str, make):
        orig = getattr(owner, name)
        setattr(owner, name, make(orig))
        self._undo.append(lambda: setattr(owner, name, orig))
        return orig

    def __enter__(self):
        from repro.federated import scheduler, servers, simulator
        probe = self

        self._patch(
            servers.PolicyServer, "receive_many",
            lambda orig: lambda srv, *a, **k: probe._receive_many(
                orig, srv, *a, **k))
        self._patch(servers, "make_server",
                    lambda orig: lambda *a, **k: probe._got_server(
                        orig(*a, **k)))
        self._patch(simulator, "_make_cohort_engine",
                    lambda orig: lambda *a, **k: probe._engine(orig, a, k))
        self._patch(simulator, "make_sketch_fn_flat",
                    lambda orig: lambda *a, **k: probe._got_sketch(
                        orig(*a, **k), a))
        self._patch(scheduler.Dispatcher, "dispatch_many",
                    lambda orig: lambda disp, *a, **k: probe._dispatch(
                        orig, disp, *a, **k))
        if self.spans_on:
            self._patch(simulator, "_make_eval",
                        lambda orig: lambda *a, **k: probe._wrap(
                            "eval", orig(*a, **k)))
        return self

    def __exit__(self, *exc):
        while self._undo:
            self._undo.pop()()
        return False

    def measure(self) -> None:
        """End the warm pass: the next experiment is the measured one."""
        self.state = "prefix"
        self.updates = 0

    def release(self) -> None:
        """Drop every reference to the program's state (the slab, the
        server's buffers), so that it can be freed."""
        self.engine = self.dispatcher_result = None

    # -- spans --------------------------------------------------------------

    def _span(self, name, fn, *a, **k):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            out = fn(*a, **k)
        self.spans.append((name, t0, time.perf_counter()))
        return out

    def _wrap(self, name, fn):
        def call(*a, **k):
            if self.state == "warm":
                return fn(*a, **k)
            return self._span(name, fn, *a, **k)
        return call

    def _dispatch(self, orig, disp, *a, **k):
        self.dispatcher_result = disp.result
        if self.spans_on and self.state != "warm":
            return self._span("dispatch_many", orig, disp, *a, **k)
        return orig(disp, *a, **k)

    # -- the engine, the server, the sketch ---------------------------------

    def _got_server(self, server):
        if self.state != "warm" and server.psa is not None:
            self.record.init_sketch = np.asarray(server.psa.global_sketch)
        return server

    def _engine(self, orig, args, kwargs):
        """The warm pass builds the engine; the measured pass gets the same
        engine back."""
        if self.engine is not None:
            return self.engine
        engine = orig(*args, **kwargs)
        self.engine = engine
        real = engine.cohort_update
        engine.cohort_update = lambda ps, cids, lrs, seeds: self._cohort(
            real, engine, ps, cids, lrs, seeds)
        return engine

    def _got_sketch(self, fn, args):
        from repro.federated.cohort import bucket_size
        kind = self.engine._data_kind
        k = int(args[2].sketch_k)

        def sketch(w_stack):
            b = int(w_stack.shape[0])
            if self.state == "warm":
                if b in self._seen_sketches:
                    return jnp.zeros((b, k), jnp.float32)
                self._seen_sketches.add(b)
                return fn(w_stack)
            if not self.spans_on:
                return fn(w_stack)
            self.sketch_calls.append((time.perf_counter(),
                                      bucket_size(b, kind)))
            return self._span("sketch", fn, w_stack)

        return sketch

    def _cohort(self, real, engine, ps, cids, lrs, seeds):
        from repro.federated.cohort import bucket_size
        b = len(cids)
        if self.state == "warm":
            if b in self._seen_waves:
                return jnp.zeros_like(ps), ps
            self._seen_waves.add(b)
            return real(ps, cids, lrs, seeds)
        if not self.spans_on:
            return real(ps, cids, lrs, seeds)
        from repro.common import obs
        out = self._span("cohort_update", real, ps, cids, lrs, seeds)
        _, t0, t1 = self.spans[-1]
        # the trip count every row ran: the program's record of this call
        (wave,) = obs.records("cohort.wave", t0, t1)
        cids = np.asarray(cids, np.int64)
        steps = engine.steps_per_client[cids]
        bs = np.minimum(engine.batch_size, engine.sizes[cids])
        rows = int(bucket_size(b, engine._data_kind))
        self.waves.append({
            "t": t0, "members": b, "rows": rows,
            "useful_steps": int(steps.sum()),
            "executed_steps": rows * int(wave["schedule"]),
            "samples": int((steps * bs).sum())})
        return out

    # -- ingest: the passes and the window ----------------------------------

    def _receive_many(self, orig, srv, deltas, client_params, client_ids,
                      data_sizes, v_dispatch, sketches=None):
        n = len(client_ids)
        if self.spans_on and self.state != "warm":
            out = self._span("receive_many", orig, srv, deltas,
                             client_params, client_ids, data_sizes,
                             v_dispatch, sketches)
        else:
            out = orig(srv, deltas, client_params, client_ids, data_sizes,
                       v_dispatch, sketches)
        self.updates += n
        if self.state == "warm":
            if self.updates >= self.warm_updates:
                jax.block_until_ready(srv.state.params)
                raise WarmPassDone()
        elif self.state == "prefix":
            if not self.record.full:
                updated, _, snaps = out
                self.record.add(client_ids, v_dispatch, deltas, sketches,
                                updated, snaps)
                self.record.kappas = [np.asarray(e["kappas"])
                                      for e in srv.log[:AGGREGATIONS]]
            if self.updates >= self.prefix and self.record.full:
                jax.block_until_ready(srv.state.params)
                self.state = "window"
                if self.dispatcher_result is not None:
                    self.dropped_at_start = self.dispatcher_result.dropped
                if self.on_window_start is not None:
                    self.on_window_start()
                self.versions[0] = srv.version
                self.t_start = time.perf_counter()
        elif self.state == "window":
            self.window_updates += n
            if self.window_updates >= self.window_target:
                jax.block_until_ready(srv.state.params)
                self.t_end = time.perf_counter()
                self.versions[1] = srv.version
                self.state = "closed"
                if self.on_window_end is not None:
                    self.on_window_end()
                raise WindowClosed()
        return out

    # -- readings -----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def in_window(self, t: float) -> bool:
        return self.t_start <= t < self.t_end

    def window_spans(self) -> List[tuple]:
        return [s for s in self.spans if self.in_window(s[1])]

    def counters(self) -> Dict[str, float]:
        waves = [w for w in self.waves if self.in_window(w["t"])]
        return {"waves": len(waves),
                "members": sum(w["members"] for w in waves),
                "rows": sum(w["rows"] for w in waves),
                "useful_steps": sum(w["useful_steps"] for w in waves),
                "executed_steps": sum(w["executed_steps"] for w in waves),
                "samples": sum(w["samples"] for w in waves),
                "updates": self.window_updates,
                "aggregations": self.versions[1] - self.versions[0],
                "sketch_rows": sum(r for t, r in self.sketch_calls
                                   if self.in_window(t))}
