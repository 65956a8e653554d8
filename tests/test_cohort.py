"""Cohort client engine: parity with the legacy per-client loop, and the
batched-arrival simulator against the sequential oracle.

The engine's contract is *exactness*, not approximation: it must visit the
same batches in the same order with the same arithmetic as
``client.local_update``, and the batched drain must reproduce the sequential
event loop's receive order, RNG streams, and per-dispatch lr/seed
assignment. CPU-only, QUICK-world sized.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tree as tu
from repro.configs import get_config
from repro.core import PSAConfig
from repro.data import (ClientDataset, StackedClients, dirichlet_partition,
                        iid_partition, make_calibration_batch,
                        make_classification, train_test_split)
from repro.federated import SimConfig, run_algorithm
from repro.federated import client as client_lib
from repro.federated.cohort import CohortEngine
from repro.models import model as M


@pytest.fixture(scope="module")
def world():
    cfg = get_config("paper-synthetic-mlp")
    full = make_classification(4_000, 10, 32, seed=0, class_sep=0.7)
    train, test = train_test_split(full, 0.1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, train, test, params


def _engine_for(cfg, params, datasets, **kw):
    spec = tu.FlatSpec(params)
    stacked = StackedClients.from_datasets(datasets)
    return spec, CohortEngine(cfg, stacked, spec, params, **kw)


def _assert_parity(cfg, params, datasets, *, epochs, batch_size, tol=1e-5,
                   **variant):
    spec, eng = _engine_for(cfg, params, datasets, local_epochs=epochs,
                            batch_size=batch_size, **variant)
    flat = jnp.array(spec.flatten(params), copy=True)
    cids = [0, len(datasets) // 2, len(datasets) - 1, 0]
    lrs = [0.01, 0.008, 0.012, 0.01]
    seeds = [11, 22, 33, 44]
    deltas, w = eng.cohort_update(jnp.stack([flat] * len(cids)), cids, lrs,
                                  seeds)
    for i, (c, lr, s) in enumerate(zip(cids, lrs, seeds)):
        ref, w_ref = client_lib.local_update(
            params, cfg, datasets[c], epochs=epochs, batch_size=batch_size,
            lr=lr, seed=s, **variant)
        err = float(jnp.max(jnp.abs(deltas[i] - spec.flatten(ref))))
        assert err <= tol, (c, err)
        err_w = float(jnp.max(jnp.abs(w[i] - spec.flatten(w_ref))))
        assert err_w <= tol, (c, err_w)


def test_parity_uniform_sizes(world):
    cfg, train, _, params = world
    parts = iid_partition(train, 8, seed=0)       # equal-size shards
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    _assert_parity(cfg, params, datasets, epochs=5, batch_size=64)


def test_parity_ragged_sizes(world):
    cfg, train, _, params = world
    parts = dirichlet_partition(train, 8, alpha=0.1, seed=0)  # ragged shards
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    sizes = sorted(len(d) for d in datasets)
    assert sizes[0] != sizes[-1], "world not ragged enough to test padding"
    _assert_parity(cfg, params, datasets, epochs=3, batch_size=64)


def test_parity_prox_and_align_variants(world):
    cfg, train, _, params = world
    parts = dirichlet_partition(train, 6, alpha=0.3, seed=1)
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    _assert_parity(cfg, params, datasets, epochs=2, batch_size=32, prox=0.5)
    _assert_parity(cfg, params, datasets, epochs=2, batch_size=32, align=0.1)


def test_cohort_padding_rows_are_noops(world):
    """Bucketed padding must not leak into real members' results."""
    cfg, train, _, params = world
    parts = iid_partition(train, 8, seed=0)
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    spec, eng = _engine_for(cfg, params, datasets, local_epochs=2,
                            batch_size=64)
    flat = jnp.array(spec.flatten(params), copy=True)
    # B=3 pads to 4; B=3 alone vs as a prefix of B=4 must agree exactly
    d3, _ = eng.cohort_update(jnp.stack([flat] * 3), [0, 1, 2],
                              [0.01] * 3, [5, 6, 7])
    d4, _ = eng.cohort_update(jnp.stack([flat] * 4), [0, 1, 2, 3],
                              [0.01] * 4, [5, 6, 7, 8])
    np.testing.assert_array_equal(np.asarray(d3), np.asarray(d4[:3]))


# -- the wave's trip count ---------------------------------------------------

@pytest.fixture(scope="module")
def ragged(world):
    """A Dirichlet-0.1 split (2 epochs at batch 64) and a wave of its three
    shortest clients, whose longest member stops short of ``num_steps``."""
    cfg, train, _, params = world
    parts = dirichlet_partition(train, 8, alpha=0.1, seed=0)
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    spec, eng = _engine_for(cfg, params, datasets, local_epochs=2,
                            batch_size=64)
    short = [int(c) for c in np.argsort(eng.steps_per_client,
                                        kind="stable")[:3]]
    assert eng.steps_per_client[short].max() < eng.num_steps
    flat = jnp.array(spec.flatten(params), copy=True)
    return cfg, params, datasets, spec, eng, short, jnp.stack([flat] * 3)


def test_short_wave_stops_at_its_longest_member(ragged, monkeypatch):
    """The jitted ``run`` on one short wave's operands: the wave's own trip
    count and the engine-wide ``num_steps`` give the same deltas to the
    bit, so the steps the cut leaves out were exact no-ops."""
    _, _, _, _, eng, short, stack = ragged
    calls, run = [], eng._run

    def spy(*args):
        calls.append(args)
        return run(*args)

    monkeypatch.setattr(eng, "_run", spy)
    d, w = eng.cohort_update(stack, short, [0.01, 0.008, 0.012], [5, 6, 7])
    (args,) = calls
    assert int(args[-1]) == eng.steps_per_client[short].max()
    d_full, w_full = run(*args[:-1], np.int32(eng.num_steps))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_full[:3]))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_full[:3]))


def test_one_program_serves_every_trip_count(ragged, monkeypatch):
    """Waves of one row bucket with different trip counts run on one
    compiled program: the trip count is traced, never static."""
    from repro.common import obs
    from repro.models import registry
    cfg, _, _, _, eng, short, stack = ragged
    run, _ = CohortEngine._build(cfg, eng.spec, eng.prox, eng.align,
                                 registry.get_family(cfg), eng.member_kernel)
    monkeypatch.setattr(eng, "_run", run)
    longest = int(np.argmax(eng.steps_per_client))
    t0 = time.perf_counter()
    for cids in ([short[0]], short[1:], short, [short[0], longest]):
        eng.cohort_update(stack[:len(cids)], cids, [0.01] * len(cids),
                          list(range(len(cids))))
        assert run._cache_size() == 1
    waves = obs.records("cohort.wave", t0, time.perf_counter())
    assert {w["rows"] for w in waves} == {4}
    assert len({w["schedule"] for w in waves}) >= 3


def test_sweep_and_streaming_match_cohort_on_a_short_wave(ragged):
    """The lane program and the streaming engine's two programs cut the
    same short wave at the same trip count: their deltas equal
    ``cohort_update``'s."""
    from repro.data import ClientSlabStore
    from repro.federated.cohort import StreamingCohortEngine
    cfg, params, datasets, spec, eng, short, stack = ragged
    lrs, seeds = [0.01, 0.008, 0.012], [5, 6, 7]
    d, _ = eng.cohort_update(stack, short, lrs, seeds)
    store = ClientSlabStore.build(datasets, shard_size=3, cache_shards=2,
                                  promote=1)
    streaming = StreamingCohortEngine(cfg, store, spec, params,
                                      local_epochs=2, batch_size=64)
    lanes = np.asarray([seeds, seeds])
    got = {
        "sweep": eng.sweep_update(jnp.stack([stack] * 2), short, lrs,
                                  lanes)[0],
        "streaming": streaming.cohort_update(stack, short, lrs, seeds)[0],
        "streaming sweep": streaming.sweep_update(
            jnp.stack([stack] * 2), short, lrs, lanes)[0],
    }
    for name, deltas in got.items():
        deltas = np.asarray(deltas).reshape((-1,) + d.shape)
        for lane in deltas:
            np.testing.assert_array_equal(lane, np.asarray(d), err_msg=name)


QUICK = dict(num_clients=16, horizon=10_000, eval_every=5_000, seed=0)


@pytest.fixture(scope="module")
def sim_world():
    cfg = get_config("paper-synthetic-mlp")
    full = make_classification(6_000, 10, 32, seed=0, class_sep=0.7)
    train, test = train_test_split(full, 0.1)
    parts = dirichlet_partition(train, 16, alpha=0.1, seed=0)
    clients = [ClientDataset(train.subset(ix)) for ix in parts]
    calib = make_calibration_batch(train, 64, "gaussian")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, clients, test, calib, params


def _orders(res):
    return [(e["t"], e["client"], e["tau"]) for e in res.receive_log]


@pytest.mark.parametrize("alg", ["fedasync", "fedbuff", "fedpsa", "ca2fl"])
def test_batched_drain_matches_sequential(sim_world, alg):
    """Same receive order, same version count, same final accuracy."""
    cfg, clients, test, calib, params = sim_world
    kw = dict(psa_cfg=PSAConfig(queue_len=10), calib_batch=calib) \
        if alg == "fedpsa" else {}
    seq = run_algorithm(alg, cfg, params, clients, test,
                        SimConfig(engine="sequential", **QUICK), **kw)
    coh = run_algorithm(alg, cfg, params, clients, test,
                        SimConfig(engine="cohort", **QUICK), **kw)
    assert _orders(seq) == _orders(coh)
    assert seq.versions == coh.versions
    assert seq.dispatches == coh.dispatches
    assert seq.times == coh.times
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)
    np.testing.assert_allclose(coh.accuracies, seq.accuracies, atol=1e-4)


def test_batched_drain_deterministic(sim_world):
    cfg, clients, test, calib, params = sim_world
    sim = SimConfig(engine="cohort", **QUICK)
    r1 = run_algorithm("fedbuff", cfg, params, clients, test, sim)
    r2 = run_algorithm("fedbuff", cfg, params, clients, test, sim)
    assert r1.final_accuracy == r2.final_accuracy
    assert _orders(r1) == _orders(r2)
    assert r1.times == r2.times


def test_fedavg_cohort_matches_sequential(sim_world):
    cfg, clients, test, calib, params = sim_world
    seq = run_algorithm("fedavg", cfg, params, clients, test,
                        SimConfig(engine="sequential", **QUICK))
    coh = run_algorithm("fedavg", cfg, params, clients, test,
                        SimConfig(engine="cohort", **QUICK))
    assert seq.versions == coh.versions and seq.dispatches == coh.dispatches
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)


def test_dropout_scenarios(sim_world):
    """Availability dropouts: identical across engines, and the slots keep
    cycling (dropped dispatches re-dispatch instead of starving)."""
    cfg, clients, test, calib, params = sim_world
    base = dict(availability_kind="hetero", dropout_rate=0.3, **QUICK)
    seq = run_algorithm("fedbuff", cfg, params, clients, test,
                        SimConfig(engine="sequential", **base))
    coh = run_algorithm("fedbuff", cfg, params, clients, test,
                        SimConfig(engine="cohort", **base))
    assert seq.dropped == coh.dropped > 0
    assert _orders(seq) == _orders(coh)
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)
    assert coh.dispatches > 0

    nodrop = run_algorithm("fedbuff", cfg, params, clients, test,
                           SimConfig(engine="cohort", **QUICK))
    assert nodrop.dropped == 0
    # dropping work can only reduce how many updates land by the horizon
    assert coh.dispatches <= nodrop.dispatches


def test_slow_fragile_availability(sim_world):
    cfg, clients, test, calib, params = sim_world
    sim = SimConfig(engine="cohort", availability_kind="slow-fragile",
                    dropout_rate=0.25, **QUICK)
    r = run_algorithm("fedasync", cfg, params, clients, test, sim)
    assert r.dropped > 0 and np.isfinite(r.final_accuracy)


def test_policy_without_raw_step_still_runs_batched(sim_world, monkeypatch):
    """A policy registered docs-style without ``raw_step`` (pre-batching
    convention) must still work under the cohort engine — receive_many
    degrades to per-event ingest instead of crashing."""
    import dataclasses as dc
    from repro.federated import policies as pol

    orig = pol.make_policy

    def no_raw(name, spec, **kw):
        return dc.replace(orig(name, spec, **kw), raw_step=None)

    monkeypatch.setattr(pol, "make_policy", no_raw)
    pol._POLICY_CACHE.clear()
    cfg, clients, test, calib, params = sim_world
    coh = run_algorithm("fedasync", cfg, params, clients, test,
                        SimConfig(engine="cohort", **QUICK))
    monkeypatch.undo()
    pol._POLICY_CACHE.clear()
    seq = run_algorithm("fedasync", cfg, params, clients, test,
                        SimConfig(engine="sequential", **QUICK))
    assert _orders(coh) == _orders(seq)
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)


def test_aulc_uses_actual_horizon():
    from repro.federated.simulator import SimResult
    r_day = SimResult(times=[0.0, 43_200.0, 86_400.0],
                      accuracies=[0.0, 0.5, 0.5])
    r_short = SimResult(times=[0.0, 5_000.0, 10_000.0],
                        accuracies=[0.0, 0.5, 0.5])
    # same curve shape => same normalized AULC regardless of horizon
    np.testing.assert_allclose(r_day.aulc, r_short.aulc)
    assert 0.0 < r_short.aulc < 1.0
