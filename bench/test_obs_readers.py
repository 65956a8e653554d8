"""The readers of the program's own spans and counters
(``cohort.row_fill_share``, ``cohort.step_fill_share``,
``host.critical_ms_per_wave``), by hand on a fabricated window and ring;
and that each reads nothing, without raising, where there is nothing to
read: no waves, or a program that keeps no records."""
from __future__ import annotations

import sys

import pytest

from bench import run

T0, T1 = 10.0, 20.0


def _ctx(counters=None):
    c = {"waves": 0, "updates": 0, "aggregations": 0, "useful_steps": 0,
         "executed_steps": 0, "samples": 0, "sketch_rows": 0,
         "members": 0, "rows": 0}
    c.update(counters or {})
    return run.Context(config={}, traffic={}, peaks=None, params=0,
                       t_start=T0, t_end=T1, window_s=T1 - T0, counters=c,
                       spans=[], compile_setup_s=0.0, compiles_window=0,
                       trace=None)


@pytest.fixture
def ring(monkeypatch):
    """A fresh recorder in the program's place."""
    import repro.common
    from repro.common import obs
    rec = obs.Recorder()
    monkeypatch.setattr(repro.common, "obs", rec)
    return rec


def _span(rec, name, t0, t1):
    # a span at given times: the ring's own entry form, in nanoseconds
    rec._spans.append((name, round(t0 * 1e9), round(t1 * 1e9), {}))


@pytest.fixture
def no_program_records(monkeypatch):
    """A program without ``repro.common.obs``, as before it had one."""
    import repro.common
    monkeypatch.delattr(repro.common, "obs")
    monkeypatch.setitem(sys.modules, "repro.common.obs", None)


def test_fill_shares_by_hand(ring):
    """The probe test's wave (2 members in 4 rows, 5 of 2 x 4 steps) and
    one more (3 in 4, 10 of 3 x 4), with a wave outside the window that
    must not count: rows 5 / 8, steps 15 / 20, and their product the
    useful-step share, 15 / 32."""
    ring.record("cohort.wave", t=T0 - 1, members=1, rows=4, steps=1,
                schedule=4, samples=1)
    ring.record("cohort.wave", t=T0 + 1, members=2, rows=4, steps=5,
                schedule=4, samples=148)
    ring.record("cohort.wave", t=T0 + 2, members=3, rows=4, steps=10,
                schedule=4, samples=300)
    ctx = _ctx({"useful_steps": 15, "executed_steps": 32})
    row = run.load_metric("cohort.row_fill_share").read(ctx)
    step = run.load_metric("cohort.step_fill_share").read(ctx)
    useful = run.load_metric("cohort.useful_step_share").read(ctx)
    assert row == pytest.approx(62.5)
    assert step == pytest.approx(75.0)
    assert row * step / 100 == pytest.approx(useful) == pytest.approx(
        100 * 15 / 32)


@pytest.mark.parametrize("name", ["cohort.row_fill_share",
                                  "cohort.step_fill_share",
                                  "host.critical_ms_per_wave"])
def test_program_readers_without_waves(ring, name):
    ring.record("cohort.wave", t=T1, members=2, rows=4, steps=5,
                schedule=4, samples=148)
    _span(ring, "cohort.enqueue", T1 + 0.1, T1 + 0.2)
    assert run.load_metric(name).read(_ctx()) is None


@pytest.mark.parametrize("name", ["cohort.row_fill_share",
                                  "cohort.step_fill_share",
                                  "host.critical_ms_per_wave"])
def test_program_readers_without_the_recorder(no_program_records, name):
    assert run.load_metric(name).read(_ctx()) is None


def test_critical_path_by_hand(ring):
    """Wave 1: the prefix's last wait ends before the window, so its
    critical path runs from the window's start to the end of its enqueue
    (4 ms). Wave 2: two waits (an eval, then the ingest's), and the path
    runs from the later one's end (10 ms). A third enqueue ends after the
    window and does not count."""
    _span(ring, "ingest.wait", T0 - 0.5, T0 - 0.1)
    _span(ring, "cohort.enqueue", T0 + 0.002, T0 + 0.004)
    _span(ring, "eval", T0 + 0.5, T0 + 0.7)
    _span(ring, "ingest.wait", T0 + 0.7, T0 + 0.9)
    _span(ring, "dispatch", T0 + 0.901, T0 + 0.902)
    _span(ring, "cohort.enqueue", T0 + 0.905, T0 + 0.910)
    _span(ring, "ingest.wait", T1 - 0.1, T1 - 0.05)
    _span(ring, "cohort.enqueue", T1 - 0.01, T1 + 0.01)
    got = run.load_metric("host.critical_ms_per_wave").read(_ctx())
    assert got == pytest.approx((4.0 + 10.0) / 2)
