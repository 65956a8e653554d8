"""The program's recorder (``repro.common.obs``): a bounded, ordered ring;
counts that agree with the benchmark's own and with the simulator's
result; spans that land on a profiler trace's clock; and numbers that
tracing leaves as they were (the golden trajectories)."""
import glob
import os
import sys
import time

import numpy as np
import pytest

import jax

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

from repro.common import obs  # noqa: E402


def test_ring_is_bounded_and_ordered():
    rec = obs.Recorder(size=4)
    for i in range(6):
        with rec.span("s", i=i):
            pass
        rec.record("c", n=i)
    got = rec.spans()
    assert [s[3]["i"] for s in got] == [2, 3, 4, 5]
    assert all(a[2] <= b[2] for a, b in zip(got, got[1:]))
    assert all(t0 <= t1 for _, t0, t1, _ in got)
    assert [c["n"] for c in rec.records("c")] == [2, 3, 4, 5]
    # the totals cover what the ring dropped
    tot = rec.totals()
    assert tot["spans"]["s"]["count"] == 6
    assert tot["counters"]["c"] == {"records": 6, "n": 15}
    assert "s" in rec.summary() and "n=15" in rec.summary()


def test_nested_spans_close_in_order():
    rec = obs.Recorder()
    with rec.span("outer"):
        with rec.span("inner"):
            time.sleep(0.001)
    (n1, a1, b1, _), (n2, a2, b2, _) = rec.spans()
    assert (n1, n2) == ("inner", "outer")
    assert a2 <= a1 < b1 <= b2


def test_windows_select_by_overlap_and_stamp():
    rec = obs.Recorder()
    rec.record("c", t=1.0, n=1)
    rec.record("c", t=2.0, n=2)
    rec.record("d", t=1.5, n=9)
    assert rec.records("c", 1.0, 2.0) == [{"n": 1}]
    with rec.span("a"):
        pass
    (_, t0, t1, _), = rec.spans()
    assert rec.spans(t1, t1 + 1) == []
    assert len(rec.spans(t0 - 1, t0 + 1e-9)) == 1
    assert rec.spans(names=("b",)) == []


def test_compiles_are_counted():
    before = obs.totals()["counters"].get(obs.COMPILE, {}).get("records", 0)
    jax.jit(lambda x: x * 3.0 + 0.125)(np.float32(2.0)).block_until_ready()
    after = obs.totals()["counters"][obs.COMPILE]
    assert after["records"] > before and after["seconds"] > 0


def _tiny_engine():
    """The probe test's small engine: clients of 20, 70 and 140 samples,
    one epoch at batch 32 (1, 2 and 4 steps). Returns (engine, spec,
    params)."""
    from repro.common import tree as tu
    from repro.data.loader import ClientDataset, StackedClients
    from repro.data.synthetic import SyntheticClassification
    from repro.federated.cohort import CohortEngine
    from bench import reference, run
    from bench.test_bench import TINY

    cfg = run.program_config(dict(TINY), exact=False)
    params = reference.init_weights(dict(TINY), 0)
    rng = np.random.default_rng(0)
    clients = [ClientDataset(SyntheticClassification(
        rng.standard_normal((n, 8, 8, 1)).astype(np.float32),
        rng.integers(0, 10, n), 10)) for n in (20, 70, 140)]
    spec = tu.FlatSpec(params)
    engine = CohortEngine(cfg, StackedClients.from_datasets(clients), spec,
                          params, local_epochs=1, batch_size=32)
    return engine, spec, params


def test_cohort_wave_counts_match_the_probe():
    """The engine's ``cohort.wave`` record and the benchmark probe's own
    count of the same wave, on the probe test's small engine: clients of
    20, 70 and 140 samples, one epoch at batch 32 (1, 2 and 4 steps); a
    wave of clients 0 and 2 in a 4-row bucket of the 4-step schedule."""
    from bench.probe import Probe

    engine, spec, params = _tiny_engine()
    probe = Probe({"prefix_updates": 1, "rate_hint": 1.0}, 1.0, spans=True)
    probe.state = "window"
    w = jax.numpy.stack([spec.flatten(params)] * 2)
    t0 = time.perf_counter()
    probe._cohort(engine.cohort_update, engine, w, [0, 2], [0.01] * 2,
                  [1, 2])
    (wave,) = obs.records("cohort.wave", t0, time.perf_counter())
    mine = probe.waves[-1]
    assert wave == {"members": 2, "rows": 4, "steps": 5, "schedule": 4,
                    "samples": 148}
    assert (wave["members"], wave["rows"], wave["steps"],
            wave["rows"] * wave["schedule"], wave["samples"]) == (
        mine["members"], mine["rows"], mine["useful_steps"],
        mine["executed_steps"], mine["samples"])


@pytest.mark.parametrize("cids, schedule", [([0], 1), ([1, 0], 2),
                                             ([1, 0, 2], 4)])
def test_cohort_wave_schedule_is_the_trip_count(cids, schedule):
    """``cohort.wave``'s ``schedule`` is the trip count the wave ran: its
    longest member's steps on ``_tiny_engine`` (clients of 1, 2 and 4
    steps), and the engine-wide ``num_steps`` where the wave holds the
    longest client."""
    engine, spec, params = _tiny_engine()
    w = jax.numpy.stack([spec.flatten(params)] * len(cids))
    t0 = time.perf_counter()
    engine.cohort_update(w, cids, [0.01] * len(cids), list(range(len(cids))))
    (wave,) = obs.records("cohort.wave", t0, time.perf_counter())
    assert wave["schedule"] == schedule == max(
        engine.steps_per_client[c] for c in cids)
    assert (wave["schedule"] == engine.num_steps) == (2 in cids)


def _repro_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events, anchor = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    anchor = e.start_ns
                elif e.name.startswith(obs.TRACE_PREFIX):
                    stats = dict(e.stats)
                    events.append((e.name[len(obs.TRACE_PREFIX):],
                                   e.start_ns, e.start_ns + e.duration_ns,
                                   stats["t_host_ns"]))
    return events, anchor


def test_spans_land_on_the_trace_clock(tmp_path):
    """Each ``repro.*`` annotation carries its ring entry's start; one
    offset from a window annotation (taken the way the benchmark takes
    its window's start) maps every span onto the trace's clock."""
    f = jax.jit(lambda a: jax.numpy.tanh(a @ a).sum())
    x = jax.numpy.ones((128, 128), jax.numpy.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        t_window = time.perf_counter()
        for i in range(5):
            with obs.span("test.outer", i=i):
                with obs.span("test.inner"):
                    f(x).block_until_ready()
                time.sleep(0.002)
    jax.profiler.stop_trace()
    events, anchor = _repro_events(str(tmp_path))
    ring = obs.spans(t_window, time.perf_counter(),
                     names=("test.outer", "test.inner"))
    assert anchor is not None and len(events) == len(ring) == 10
    starts = {round(t0 * 1e9): (name, t0, t1) for name, t0, t1, _ in ring}
    offset = anchor - t_window * 1e9
    for name, a, b, t_host_ns in events:
        mine = starts.get(t_host_ns)
        assert mine is not None and mine[0] == name
        assert mine[1] == t_host_ns / 1e9
        assert abs(mine[1] * 1e9 + offset - a) < 100e3
        assert abs(mine[2] * 1e9 + offset - b) < 100e3


def _golden_run(name, streaming):
    import test_golden as golden
    from repro.core import PSAConfig
    from repro.federated import SimConfig, run_algorithm

    cfg, clients, test, calib, params = golden._build_world()
    kw = {}
    if name == "fedpsa":
        kw = dict(psa_cfg=PSAConfig(**golden.PSA), calib_batch=calib)
    shards = dict(shard_size=3, shard_cache=2, shard_promote=1)
    sim = SimConfig(engine="cohort", record_trajectory=True,
                    **(shards if streaming else {}), **golden.SIM)
    return golden, run_algorithm(name, cfg, params, clients, test, sim,
                                 **kw)


@pytest.mark.parametrize("streaming", [False, True])
def test_golden_trajectory_with_counts(streaming):
    """FedPSA's golden run on the cohort engine (resident and streamed
    slabs) reproduces its checked-in digests while the recorder runs, and
    the run's counts agree with the simulator's result: a ``cohort.wave``
    per cohort, its members and the ingest's arrivals equal to the
    dispatches, the aggregations to the versions, every engine call inside
    a wave span."""
    t0 = time.perf_counter()
    golden, res = _golden_run("fedpsa", streaming)
    t1 = time.perf_counter()
    golden._check(res, golden._load("fedpsa"))
    waves = obs.records("cohort.wave", t0, t1)
    chunks = obs.records("ingest.chunk", t0, t1)
    assert len(waves) == res.cohorts
    assert sum(w["members"] for w in waves) == res.dispatches
    assert sum(c["arrivals"] for c in chunks) == res.dispatches
    assert sum(c["aggregations"] for c in chunks) == res.versions
    assert all(w["rows"] >= w["members"] for w in waves)
    assert all(w["steps"] <= w["members"] * w["schedule"] for w in waves)
    assert len(obs.records("sketch.rows", t0, t1)) == res.cohorts
    spans = obs.spans(t0, t1)
    names = {s[0] for s in spans}
    assert names >= {"sim.wave", "sim.assemble", "sim.gather",
                     "cohort.enqueue", "sketch.enqueue", "ingest.enqueue",
                     "ingest.wait", "ingest.log", "dispatch", "eval"}
    outer = [(a, b) for n, a, b, _ in spans if n == "sim.wave"]
    for n, a, b, _ in spans:
        if n == "cohort.enqueue":
            assert any(wa <= a and b <= wb for wa, wb in outer)
