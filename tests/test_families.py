"""Model-family registry + federated LM scenario.

Fast tier: registry contract (membership, fallback warning, engine
recording) and the document-level token partition. Slow tier (the
LM-scenario marker CI runs in its own matrix entry): cohort-vs-sequential
parity on the non-paper families — the dense/ssm/moe fed-lm smokes must
train under ``engine="cohort"`` end to end with trajectories pinned to the
sequential oracle within 1e-5.
"""
import dataclasses
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tree as tu
from repro.configs import get_config
from repro.data import StackedClients, document_partition
from repro.federated import SimConfig, run_algorithm
from repro.federated import client as client_lib
from repro.federated import simulator as sim_mod
from repro.federated.cohort import CohortEngine, bucket_size, wave_axis
from repro.launch.train import build_task
from repro.models import model as M
from repro.models import registry

LM_ARCHS = ("fed-lm-smoke", "fed-lm-ssm-smoke", "fed-lm-moe-smoke")


# ---------------------------------------------------------------------------
# Registry contract (fast tier)
# ---------------------------------------------------------------------------


def test_registry_membership():
    assert registry.is_registered("cnn") and registry.is_registered("mlp")
    for fam in ("dense", "ssm", "moe", "hybrid"):
        assert registry.is_registered(fam), fam
    assert not registry.is_registered("audio")
    assert not registry.is_registered("vlm")
    with pytest.raises(KeyError, match="not in the model-family registry"):
        registry.get_family("audio")


def test_registry_entry_shapes():
    for arch in LM_ARCHS:
        cfg = get_config(arch)
        fam = registry.get_family(cfg)
        assert fam.data_kind == "tokens"
        assert fam.name == cfg.family
    assert registry.get_family(get_config("paper-synthetic-mlp")).data_kind \
        == "image"


def test_register_family_rejects_duplicates():
    entry = registry.get_family("dense")
    with pytest.raises(ValueError, match="already registered"):
        registry.register_family(entry)
    # override=True replaces (and restores) without complaint
    registry.register_family(entry, override=True)


def test_token_masked_batch_is_noop_when_unmasked():
    fam = registry.get_family("dense")
    xb = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    yb = xb + 1
    batch = fam.masked_batch(xb, yb, jnp.ones(3, jnp.float32), 3.0)
    np.testing.assert_array_equal(np.asarray(batch["labels"]), np.asarray(yb))
    masked = fam.masked_batch(xb, yb, jnp.asarray([1.0, 0.0, 1.0]), 2.0)
    assert np.all(np.asarray(masked["labels"])[1] == -1)
    np.testing.assert_array_equal(np.asarray(masked["labels"])[0],
                                  np.asarray(yb)[0])


def test_resolve_engine_consults_registry():
    sim = SimConfig(engine="cohort")
    assert sim_mod._resolve_engine(sim, get_config("paper-synthetic-mlp")) \
        == "cohort"
    assert sim_mod._resolve_engine(sim, get_config("fed-lm-smoke")) == "cohort"
    audio = get_config("hubert-xlarge").reduced()
    sim_mod._FALLBACK_WARNED.discard(audio.family)
    with pytest.warns(RuntimeWarning, match="'audio'.*sequential"):
        assert sim_mod._resolve_engine(sim, audio) == "sequential"
    # one-time: the second resolve for the same family stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert sim_mod._resolve_engine(sim, audio) == "sequential"
    sim_mod._FALLBACK_WARNED.discard(audio.family)


def test_bucket_size_grid():
    # token families: coarse {pow2, 1.5*pow2} grid (compile cost is seconds
    # per program), image families: the legacy fine multiples-of-4 grid
    assert [bucket_size(b) for b in (1, 4, 5, 6, 7, 9, 13, 17, 25, 33)] == \
        [4, 4, 6, 6, 8, 12, 16, 24, 32, 48]
    assert [bucket_size(b, "image") for b in (1, 4, 5, 9, 31)] == \
        [4, 4, 8, 12, 32]
    for b in range(1, 300):
        for kind in ("tokens", "image"):
            assert b <= bucket_size(b, kind) <= max(4, (3 * b + 1) // 2)


def test_wave_axis_splits_only_divisible_buckets():
    # the rule the cohort engine and the batched client sketch share
    mesh = types.SimpleNamespace(shape={"data": 4})
    assert wave_axis(mesh, "data", 8) == "data"
    assert wave_axis(mesh, "data", 6) is None
    assert wave_axis(mesh, None, 8) is None
    assert wave_axis(None, None, 4) is None


# ---------------------------------------------------------------------------
# Document-level token partition (fast tier)
# ---------------------------------------------------------------------------


def test_document_partition_covers_and_windows():
    seq, doc = 8, 32
    corpus = np.arange(40 * doc, dtype=np.int32)
    parts = document_partition(corpus, 5, seq, doc_len=doc, seed=0)
    assert len(parts) == 5
    rows = np.concatenate(parts)
    assert rows.shape == (40 * doc // seq, seq)
    # windows never straddle documents: every row is a consecutive run
    # starting at a multiple of seq (corpus == arange makes this checkable)
    assert np.all(rows[:, 1:] - rows[:, :-1] == 1)
    assert np.all(rows[:, 0] % seq == 0)
    # whole documents per client: each client's row count is a multiple of
    # windows-per-document
    for p in parts:
        assert p.shape[0] % (doc // seq) == 0 and p.shape[0] > 0


def test_document_partition_alpha_skews_sizes():
    corpus = np.arange(4000, dtype=np.int32)
    flat = document_partition(corpus, 4, 8, alpha=0.0, seed=0)
    skew = document_partition(corpus, 4, 8, alpha=0.1, seed=0)
    sizes_flat = [len(p) for p in flat]
    sizes_skew = [len(p) for p in skew]
    assert sum(sizes_flat) == sum(sizes_skew)
    assert max(sizes_flat) - min(sizes_flat) <= 4      # near-uniform
    assert np.std(sizes_skew) > np.std(sizes_flat)     # Dirichlet skew
    assert min(sizes_skew) >= 1


def test_token_stacked_clients_slab():
    cfg, clients, test, calib = build_task("fed-lm-smoke", 120, 0.5, 4, 0,
                                           seq_len=8)
    stacked = StackedClients.from_datasets(clients)
    assert stacked.kind == "tokens"
    assert stacked.x.dtype == np.int32 and stacked.x.ndim == 3
    assert stacked.y.shape == stacked.x.shape
    for c, d in enumerate(clients):
        n = stacked.sizes[c]
        np.testing.assert_array_equal(stacked.x[c, :n], d.data.x)
        assert not stacked.mask[c, n:].any()
    # token batches speak the loss_fn convention
    batch = next(iter(clients[0].epochs(1, 4, seed=0)))
    assert set(batch) == {"tokens", "labels"}
    assert set(calib) == {"tokens", "labels"}


# ---------------------------------------------------------------------------
# Cohort-vs-sequential parity on non-paper families (slow / LM tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_cohort_engine_parity_lm(arch):
    """The compiled vmap x scan engine reproduces client.local_update for
    dense, ssm, and moe smoke configs (ragged shards included)."""
    cfg, clients, _, _ = build_task(arch, 120, 0.5, 5, 0, seq_len=16)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    spec = tu.FlatSpec(params)
    eng = CohortEngine(cfg, StackedClients.from_datasets(clients), spec,
                       params, local_epochs=2, batch_size=8)
    flat = jnp.array(spec.flatten(params), copy=True)
    cids, lrs, seeds = [0, 2, 4], [0.01, 0.02, 0.01], [7, 8, 9]
    deltas, w = eng.cohort_update(jnp.stack([flat] * 3), cids, lrs, seeds)
    for i, (c, lr, s) in enumerate(zip(cids, lrs, seeds)):
        ref, w_ref = client_lib.local_update(params, cfg, clients[c],
                                             epochs=2, batch_size=8,
                                             lr=lr, seed=s)
        assert float(jnp.max(jnp.abs(deltas[i] - spec.flatten(ref)))) <= 1e-5
        assert float(jnp.max(jnp.abs(w[i] - spec.flatten(w_ref)))) <= 1e-5


LM_QUICK = dict(num_clients=8, horizon=3_000.0, eval_every=1_500.0, seed=0,
                local_epochs=2, batch_size=8, record_trajectory=True)


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_drain_matches_sequential(arch):
    """Full async sim on each non-paper family: the cohort engine runs end
    to end (no silent fallback) and pins the sequential oracle's receive
    order and digest trajectory within 1e-5."""
    cfg, clients, test, _ = build_task(arch, 240, 0.3, 8, 0, seq_len=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    seq = run_algorithm("fedasync", cfg, params, clients, test,
                        SimConfig(engine="sequential", **LM_QUICK))
    coh = run_algorithm("fedasync", cfg, params, clients, test,
                        SimConfig(engine="cohort", **LM_QUICK))
    assert seq.engine == "sequential" and coh.engine == "cohort"
    assert coh.cohorts > 0 and coh.dispatches > 0
    assert [(e["t"], e["client"], e["tau"]) for e in seq.receive_log] == \
        [(e["t"], e["client"], e["tau"]) for e in coh.receive_log]
    assert seq.versions == coh.versions
    np.testing.assert_allclose(np.asarray(coh.digests),
                               np.asarray(seq.digests),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)


@pytest.mark.slow
def test_lm_fedavg_and_prox_variants():
    """Synchronous FedAvg + FedProx run the token path too (the cohort
    engine's prox pull is family-agnostic flat-vector arithmetic)."""
    cfg, clients, test, _ = build_task("fed-lm-smoke", 160, 0.0, 6, 0,
                                       seq_len=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    quick = dict(num_clients=6, horizon=2_000.0, eval_every=1_000.0, seed=0,
                 local_epochs=2, batch_size=8)
    seq = run_algorithm("fedavg", cfg, params, clients, test,
                        SimConfig(engine="sequential", **quick), prox=0.1)
    coh = run_algorithm("fedavg", cfg, params, clients, test,
                        SimConfig(engine="cohort", **quick), prox=0.1)
    assert seq.versions == coh.versions and seq.dispatches == coh.dispatches
    np.testing.assert_allclose(coh.final_accuracy, seq.final_accuracy,
                               atol=1e-4)


@pytest.mark.slow
def test_lm_sim_records_engine_and_lognormal_latency():
    """SimConfig plumbing on the LM scenario: lognormal latency runs end to
    end and the result records the engine actually used."""
    cfg, clients, test, _ = build_task("fed-lm-smoke", 160, 0.3, 6, 0,
                                       seq_len=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sim = SimConfig(num_clients=6, horizon=2_000.0, eval_every=1_000.0,
                    seed=0, local_epochs=2, batch_size=8,
                    latency_kind="lognormal")
    r = run_algorithm("fedbuff", cfg, params, clients, test, sim)
    assert r.engine == "cohort"
    assert r.dispatches > 0 and np.isfinite(r.final_accuracy)
