"""``correct`` at a size a test run can hold: a small CNN and world on the
CPU, driven through the whole of a run but the look for a chip. A sound
run is correct; the control (the reference in bfloat16 in the program's
place) and every fault a one-chip cell can have (``bench.faults``) are
not, under each cell's committed limits."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import correct, faults, reference, run, traffic
from bench.test_bench import TINY

BENCH = run.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
SMALL = {"samples": 600, "clients": 12, "concurrency": 0.34, "batch": 16,
         "local_epochs": 2, "prefix_updates": 50,
         "eval_every": 1e9}
SEED = 2**31 + 7


def _limits(cell):
    return traffic.load_json("limits", cell)["limits"]


def _run(fault=None, keep=None):
    cell = run.find_cell(BENCH, "cifar10-fedpsa-dir0.1")
    return run.run_cell(cell, SEED, 0.0, False, bench=BENCH,
                        require_tpu=False, config=dict(TINY),
                        traffic_overrides=SMALL, limits={}, fault=fault,
                        keep=keep)


@pytest.fixture(scope="module")
def sound():
    keep: dict = {}
    res = _run(keep=keep)
    return res, keep


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(sound, cell):
    res, _ = sound
    values = {k: v["value"] for k, v in res["checks"].items()}
    ok, checks = correct.verdict(values, _limits(cell))
    assert ok, checks
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(sound, cell):
    _, keep = sound
    ctl = reference.replay(keep["config"], keep["traffic"], keep["world"],
                           keep["w0"], keep["record"].arrivals,
                           keep["shuffle"], dtype=jnp.bfloat16)
    values = correct.numbers(ctl, keep["want"], keep["w0_flat"],
                             reference.leaf_sizes(keep["config"]))
    ok, checks = correct.verdict(values, _limits(cell))
    assert not ok, checks


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(fault):
    res = _run(fault=fault)
    values = {k: v["value"] for k, v in res["checks"].items()}
    for cell in CELLS:
        ok, checks = correct.verdict(values, _limits(cell))
        assert not ok, (cell, checks)


def test_verdict_needs_a_limit():
    """A cell whose limits are all unset is never correct."""
    values = {name: 0.0 for name in correct.NUMBERS}
    assert not correct.verdict(values, {})[0]
    assert not correct.verdict(values, dict.fromkeys(correct.NUMBERS))[0]
    assert correct.verdict(values, {"update_gap": 0.1})[0]
