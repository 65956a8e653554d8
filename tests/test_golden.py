"""Golden-trajectory regression suite: the paper reproduction, pinned.

For every async policy, a fixed-seed QUICK world is run on the sequential
oracle and its trajectory is *checked in* as a digest stream
(``tests/golden/<policy>.json``): one ``(||w||_2, probe·w)`` fingerprint of
the flat global vector per applied receive, plus the run's final metrics.
The suite then asserts that every execution path — the sequential oracle
itself, the batched cohort engine, and the mesh-sharded server on a 2- and
4-virtual-device CPU mesh — reproduces those digests within float
tolerance. Any layout, kernel, or policy change that silently drifts the
numerics fails here instead of in the paper's tables.

Regenerate after an *intentional* numerical change with::

    make golden-regen        # runs this file with --regen

and commit the resulting ``tests/golden/`` diff (CI re-derives the digests
and fails if the committed files are stale).
"""
import json
import os
import sys

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config                       # noqa: E402
from repro.core import PSAConfig                           # noqa: E402
from repro.data import (ClientDataset, dirichlet_partition,  # noqa: E402
                        make_calibration_batch, make_classification,
                        train_test_split)
from repro.federated import SimConfig, run_algorithm       # noqa: E402
from repro.federated.policies import POLICY_NAMES          # noqa: E402
from repro.launch.mesh import make_fed_mesh                # noqa: E402
from repro.models import model as M                        # noqa: E402

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

# The golden world. Changing ANY of these constants invalidates the
# checked-in digests — regenerate and commit.
WORLD = dict(model="paper-synthetic-mlp", samples=1_500, classes=10, dim=32,
             clients=8, alpha=0.3, seed=0)
SIM = dict(num_clients=8, horizon=6_000.0, eval_every=3_000.0, seed=0)
PSA = dict(queue_len=10)   # queue fills mid-run: covers both weight phases

# Digests are compared loosely enough to absorb reduction-order float noise
# (engine/layout differences measure ~1e-6 relative) and tightly enough
# that any behavioral change — a weighting rule, a staleness resolution, a
# buffer slot — lands far outside the band within a handful of steps.
RTOL, ATOL = 1e-4, 1e-3


def _build_world():
    cfg = get_config(WORLD["model"])
    full = make_classification(WORLD["samples"], WORLD["classes"],
                               WORLD["dim"], seed=WORLD["seed"],
                               class_sep=0.7)
    train, test = train_test_split(full, 0.1)
    parts = dirichlet_partition(train, WORLD["clients"],
                                alpha=WORLD["alpha"], seed=WORLD["seed"])
    clients = [ClientDataset(train.subset(ix)) for ix in parts]
    calib = make_calibration_batch(train, 64, "gaussian")
    params = M.init_params(jax.random.PRNGKey(WORLD["seed"]), cfg)
    return cfg, clients, test, calib, params


@pytest.fixture(scope="module")
def world():
    return _build_world()


def _run(world, name, engine, mesh=None):
    cfg, clients, test, calib, params = world
    kw = {}
    if name == "fedpsa":
        kw = dict(psa_cfg=PSAConfig(**PSA), calib_batch=calib)
    sim = SimConfig(engine=engine, mesh=mesh, record_trajectory=True, **SIM)
    return run_algorithm(name, cfg, params, clients, test, sim, **kw)


def _golden_path(name):
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def _load(name):
    path = _golden_path(name)
    assert os.path.exists(path), \
        f"missing golden digests {path} — run `make golden-regen` and commit"
    with open(path) as f:
        return json.load(f)


def _final(result):
    return {"final_accuracy": result.final_accuracy,
            "versions": result.versions,
            "dispatches": result.dispatches,
            "dropped": result.dropped,
            "launched": result.launched}


def _check(result, golden):
    want = golden["digests"]
    assert len(result.digests) == len(want), \
        (len(result.digests), len(want))
    np.testing.assert_allclose(np.asarray(result.digests),
                               np.asarray(want), rtol=RTOL, atol=ATOL)
    final = _final(result)
    for key in ("versions", "dispatches", "dropped", "launched"):
        assert final[key] == golden["final"][key], key
    np.testing.assert_allclose(final["final_accuracy"],
                               golden["final"]["final_accuracy"], atol=2e-3)
    # the curve shape, not just its endpoint (catches eval-grid drift)
    np.testing.assert_allclose(result.aulc, golden["final"]["aulc"],
                               atol=2e-3)


@pytest.mark.parametrize("name", POLICY_NAMES)
def test_sequential_matches_golden(world, name):
    """The oracle itself reproduces its checked-in trajectory."""
    _check(_run(world, name, "sequential"), _load(name))


@pytest.mark.parametrize("name", POLICY_NAMES)
def test_cohort_matches_golden(world, name):
    """The batched cohort engine reproduces the oracle's digests."""
    _check(_run(world, name, "cohort"), _load(name))


@pytest.mark.parametrize("name", POLICY_NAMES)
def test_streaming_matches_golden(world, name):
    """The chunked/streaming engine — client slabs split into 3-client
    shards behind a 2-shard LRU cache, so the golden run is forced through
    multiple shard loads AND at least one eviction — reproduces the same
    digest stream as the monolithic stacked-slab engine."""
    cfg, clients, test, calib, params = world
    kw = {}
    if name == "fedpsa":
        kw = dict(psa_cfg=PSAConfig(**PSA), calib_batch=calib)
    sim = SimConfig(engine="cohort", record_trajectory=True,
                    shard_size=3, shard_cache=2, shard_promote=1, **SIM)
    _check(run_algorithm(name, cfg, params, clients, test, sim, **kw),
           _load(name))


@pytest.mark.multidevice
@pytest.mark.parametrize("ndev", (2, 4))
@pytest.mark.parametrize("name", POLICY_NAMES)
def test_sharded_matches_golden(world, name, ndev):
    """The mesh-sharded server + data-parallel cohort engine reproduce the
    same digests on 2- and 4-device CPU meshes
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices, have {jax.device_count()}")
    _check(_run(world, name, "cohort", mesh=make_fed_mesh(ndev)), _load(name))


def test_digest_barely_depends_on_the_batch():
    """A row's digest alone and inside a wave agree to f64 rounding, far
    below the engines' tolerance (f32 sums differed by ~1e-5)."""
    from repro.federated.simulator import make_digest_fn
    rows = np.random.RandomState(0).randn(7, 100_003).astype(np.float32)
    fn = make_digest_fn(rows.shape[1])
    whole = fn(rows)
    for i in range(len(rows)):
        np.testing.assert_allclose(fn(rows[i:i + 1])[0], whole[i],
                                   rtol=1e-12)


def test_golden_digests_are_committed():
    """Every policy has its digest file (regen writes all seven at once)."""
    for name in POLICY_NAMES:
        assert os.path.exists(_golden_path(name)), name


# One timeline-preserving hyper override per policy — a lane that must
# DIFFER from the default lane (proving per-lane hyper actually bites).
SWEEP_HYPER = {
    "fedasync": {"alpha": 0.3}, "fedbuff": {"server_lr": 0.7},
    "fedpsa": {"server_lr": 0.5}, "ca2fl": {"server_lr": 0.6},
    "fedfa": {"beta": 0.8}, "fedpac": {"server_lr": 0.8},
    "asyncfeded": {"alpha": 0.4},
}


@pytest.mark.parametrize("name", POLICY_NAMES)
def test_sweep_lane_matches_golden(world, name):
    """The sweep case: lane 0 of a 3-lane ``run_sweep`` (default seeds and
    hyperparameters, shared timeline) reproduces the checked-in golden
    digest stream, while the hyper-varied and reshuffled lanes diverge from
    it — lanes are independent simulations riding one compiled program."""
    from repro.federated import SweepConfig, run_sweep

    cfg, clients, test, calib, params = world
    kw = {}
    if name == "fedpsa":
        kw = dict(psa_cfg=PSAConfig(**PSA), calib_batch=calib)
    sweep = SweepConfig(data_seeds=[SIM["seed"], SIM["seed"], 1234],
                        policy_params=[None, SWEEP_HYPER[name], None])
    sim = SimConfig(engine="cohort", record_trajectory=True, **SIM)
    res = run_sweep(name, cfg, params, clients, test, sim, sweep, **kw)
    golden = _load(name)
    want = np.asarray(golden["digests"])
    got = np.asarray(res.digests[0])
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(res.final_accuracy[0],
                               golden["final"]["final_accuracy"], atol=2e-3)
    assert res.dispatches == golden["final"]["dispatches"]
    assert res.launched == golden["final"]["launched"]
    # the varied lanes must NOT reproduce the default trajectory
    for s in (1, 2):
        assert not np.allclose(np.asarray(res.digests[s]), want,
                               rtol=RTOL, atol=ATOL), s


# ---------------------------------------------------------------------------
# Federated LM scenario golden (fed-lm-smoke, slow / LM tier)
# ---------------------------------------------------------------------------

# The token-slab world: a dense-transformer smoke fine-tuned across
# document-partitioned bigram corpus shards. Changing ANY of these constants
# (or the fed-lm-smoke config) invalidates tests/golden/fed-lm-smoke.json.
FED_LM_WORLD = dict(model="fed-lm-smoke", samples=240, clients=6, alpha=0.3,
                    seed=0, seq=16)
FED_LM_SIM = dict(num_clients=6, horizon=6_000.0, eval_every=3_000.0, seed=0,
                  local_epochs=2, batch_size=8)
FED_LM_POLICIES = ("fedasync", "fedpsa")


def _build_lm_world():
    from repro.launch.train import build_task
    cfg, clients, test, calib = build_task(
        FED_LM_WORLD["model"], FED_LM_WORLD["samples"], FED_LM_WORLD["alpha"],
        FED_LM_WORLD["clients"], FED_LM_WORLD["seed"],
        seq_len=FED_LM_WORLD["seq"])
    params = M.init_params(jax.random.PRNGKey(FED_LM_WORLD["seed"]), cfg)
    return cfg, clients, test, calib, params


@pytest.fixture(scope="module")
def lm_world():
    return _build_lm_world()


def _run_lm(world, name, engine):
    cfg, clients, test, calib, params = world
    kw = {}
    if name == "fedpsa":
        kw = dict(psa_cfg=PSAConfig(**PSA), calib_batch=calib)
    sim = SimConfig(engine=engine, record_trajectory=True, **FED_LM_SIM)
    return run_algorithm(name, cfg, params, clients, test, sim, **kw)


@pytest.mark.slow
@pytest.mark.parametrize("engine", ("sequential", "cohort"))
@pytest.mark.parametrize("name", FED_LM_POLICIES)
def test_fed_lm_matches_golden(lm_world, name, engine):
    """Both engines reproduce the checked-in LM-scenario digest streams
    (and the cohort run must actually BE a cohort run, not a fallback)."""
    result = _run_lm(lm_world, name, engine)
    assert result.engine == engine
    _check(result, _load("fed-lm-smoke")["policies"][name])


# ---------------------------------------------------------------------------
# Regeneration entry point (make golden-regen)
# ---------------------------------------------------------------------------

def _round(x, sig=6):
    """Quantize to 6 significant digits: far below the comparison tolerance,
    above cross-run float noise, so regen on an unchanged tree is a no-op
    diff (the CI staleness gate relies on this)."""
    return float(f"{float(x):.{sig}g}")


def regen():
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    world = _build_world()
    for name in POLICY_NAMES:
        r = _run(world, name, "sequential")
        final = _final(r)
        final["final_accuracy"] = _round(final["final_accuracy"])
        final["aulc"] = _round(r.aulc)
        payload = {
            "world": WORLD, "sim": SIM,
            "psa": PSA if name == "fedpsa" else None,
            "policy": name,
            "digests": [[_round(a), _round(b)] for a, b in r.digests],
            "final": final,
        }
        path = _golden_path(name)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"wrote {path}  ({len(r.digests)} digests, "
              f"acc={final['final_accuracy']:.4f})")
    lm_world = _build_lm_world()
    policies = {}
    for name in FED_LM_POLICIES:
        r = _run_lm(lm_world, name, "sequential")
        final = _final(r)
        final["final_accuracy"] = _round(final["final_accuracy"])
        final["aulc"] = _round(r.aulc)
        policies[name] = {
            "digests": [[_round(a), _round(b)] for a, b in r.digests],
            "final": final,
        }
    payload = {"world": FED_LM_WORLD, "sim": FED_LM_SIM, "psa": PSA,
               "policies": policies}
    path = _golden_path("fed-lm-smoke")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {path}  ({[len(p['digests']) for p in policies.values()]} "
          f"digests)")


def check() -> int:
    """Staleness gate for CI: re-derive every policy's trajectory from the
    sequential oracle and compare against the COMMITTED digests within the
    suite's tolerance (never bitwise — float low bits differ across
    BLAS/SIMD/jax builds, and a byte-diff gate would flap on them). Exits
    non-zero when a numerical change landed without `make golden-regen` +
    committing the ``tests/golden/`` diff."""
    world = _build_world()
    stale = []
    for name in POLICY_NAMES:
        try:
            _check(_run(world, name, "sequential"), _load(name))
        except AssertionError as e:
            stale.append(name)
            print(f"STALE {name}: {str(e).splitlines()[0]}", file=sys.stderr)
        else:
            print(f"ok {name}")
    lm_world = _build_lm_world()
    for name in FED_LM_POLICIES:
        try:
            _check(_run_lm(lm_world, name, "sequential"),
                   _load("fed-lm-smoke")["policies"][name])
        except AssertionError as e:
            stale.append(f"fed-lm-smoke/{name}")
            print(f"STALE fed-lm-smoke/{name}: {str(e).splitlines()[0]}",
                  file=sys.stderr)
        else:
            print(f"ok fed-lm-smoke/{name}")
    if stale:
        print(f"golden digests stale for {stale} — run `make golden-regen` "
              f"and commit tests/golden/", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--regen" in sys.argv:
        regen()
    elif "--check" in sys.argv:
        sys.exit(check())
    else:
        print("usage: python tests/test_golden.py --regen | --check",
              file=sys.stderr)
        sys.exit(2)
