"""``correct`` at a size a test run can hold: a small CNN and world on the
CPU, driven through the whole of a run but the look for a chip. A sound
run is correct; the control (the reference in bfloat16 in the program's
place) and every fault a one-chip cell can have (``bench.faults``) are
not, under each cell's committed limits.

A cell's limits are held against a small run of its mix's shape: the
cross-device mix's own (many clients of one or two steps, waves of several
members, several ingest chunks a wave), the IID mix's (dir0.1's protocol
over equal clients), and dir0.1's for every other."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench import correct, faults, reference, run, traffic
from bench.test_bench import TINY
from repro.common import obs

BENCH = run.load_benchmark()
# the cross-device cell is not in BENCHMARK.json (its first run compiles
# for longer than a run may take; PERF.md section 7); its limits are held
# against its own small run all the same
CROSSDEVICE = {"name": "mnist-fedpsa-crossdevice", "config": "paper-mnist-cnn",
               "traffic": "crossdevice", "chips": 1}
CELLS = [c["name"] for c in BENCH["workloads"]] + [CROSSDEVICE["name"]]
SMALL = {"samples": 600, "clients": 12, "concurrency": 0.34, "batch": 16,
         "local_epochs": 2, "prefix_updates": 50,
         "eval_every": 1e9}
# crossdevice's shape, small: 200 clients of 4-50 samples (1-3 steps of 16),
# 20 in flight, one epoch, lognormal latency: waves of several members
CROSS = {"samples": 4000, "clients": 200, "batch": 16, "prefix_updates": 50,
         "eval_every": 1e9}
# the IID mix's shape, small: SMALL over 12 equal clients of 45 samples
IID = dict(SMALL, alpha=0.0)
DEFAULT = "cifar10-fedpsa-dir0.1"
SHAPES = {DEFAULT: SMALL, "mnist-fedpsa-crossdevice": CROSS,
          "cifar10-fedpsa-iid": IID}
SEED = 2**31 + 7


def _limits(cell):
    return traffic.load_json("limits", cell)["limits"]


def _cell(name):
    if name == CROSSDEVICE["name"]:
        return CROSSDEVICE
    return run.find_cell(BENCH, name)


def _shape(cell):
    """The cell whose mix, with its overrides, the small run of ``cell``
    takes."""
    mix = _cell(cell)["traffic"]
    return next((name for name in SHAPES if _cell(name)["traffic"] == mix),
                DEFAULT)


def _run(shape=DEFAULT, fault=None, keep=None):
    cell = _cell(shape)
    return run.run_cell(cell, SEED, 0.0, False, bench=BENCH,
                        require_tpu=False, config=dict(TINY),
                        traffic_overrides=SHAPES[shape], limits={},
                        fault=fault, keep=keep)


@pytest.fixture(scope="module")
def sound():
    """The sound run of each shape, made when a test first asks for it."""
    runs = {}

    def get(shape):
        if shape not in runs:
            keep: dict = {}
            t0 = time.perf_counter()
            res = _run(shape, keep=keep)
            keep["span"] = (t0, time.perf_counter())
            runs[shape] = (res, keep)
        return runs[shape]
    return get


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(sound, cell):
    res, _ = sound(_shape(cell))
    values = {k: v["value"] for k, v in res["checks"].items()}
    ok, checks = correct.verdict(values, _limits(cell))
    assert ok, checks
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(sound, cell):
    _, keep = sound(_shape(cell))
    ctl = reference.replay(keep["config"], keep["traffic"], keep["world"],
                           keep["w0"], keep["record"].arrivals,
                           keep["shuffle"], dtype=jnp.bfloat16)
    values = correct.numbers(ctl, keep["want"], keep["w0_flat"],
                             reference.leaf_sizes(keep["config"]))
    ok, checks = correct.verdict(values, _limits(cell))
    assert not ok, checks


@pytest.mark.parametrize(
    "fault,shape", [(f, s) for s in SHAPES for f in faults.FAULTS],
    ids=[f if s == DEFAULT else f"{f}-{s}" for s in SHAPES
         for f in faults.FAULTS])
def test_fault_is_not_correct(fault, shape):
    res = _run(shape, fault=fault)
    values = {k: v["value"] for k, v in res["checks"].items()}
    for cell in [c for c in CELLS if _shape(c) == shape]:
        ok, checks = correct.verdict(values, _limits(cell))
        assert not ok, (cell, checks)


def test_crossdevice_shape_holds_waves_of_several_members(sound):
    """The small cross-device run is of its mix's shape: its waves hold
    several members and are ingested in more chunks than there are waves,
    its recorded arrivals are mostly trained from the initial weights, and
    it keeps the mix's latency kind and single epoch."""
    res, keep = sound("mnist-fedpsa-crossdevice")
    waves = obs.records("cohort.wave", *keep["span"])
    chunks = obs.records("ingest.chunk", *keep["span"])
    assert max(w["members"] for w in waves) >= 3
    assert len(chunks) > len(waves)
    assert keep["traffic"]["latency"]["kind"] == "lognormal"
    assert keep["traffic"]["local_epochs"] == 1
    versions = [v for _, v in keep["record"].arrivals]
    assert len(versions) == correct.ARRIVALS
    assert versions[:10] == [0] * 10
    assert res["attempted"] > 1


def test_verdict_needs_a_limit():
    """A cell whose limits are all unset is never correct."""
    values = {name: 0.0 for name in correct.NUMBERS}
    assert not correct.verdict(values, {})[0]
    assert not correct.verdict(values, dict.fromkeys(correct.NUMBERS))[0]
    assert correct.verdict(values, {"update_gap": 0.1})[0]
