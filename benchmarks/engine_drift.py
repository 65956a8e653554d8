"""Engine drift: why the cohort engine and the sequential oracle part ways.

    PYTHONPATH=src python -m benchmarks.engine_drift [--epochs 1,2,3,4,5]
        [--precisions default,float32] [--ulps N] [--out DIR]

Minutes on one TPU chip; on a CPU about 18 minutes for ``--epochs 1``.

One client update from one snapshot, at the full width of the paper's
CIFAR-10 CNN, for a short client (751 samples, 11 steps an epoch) and a
long one (3,470 samples, 54 steps an epoch: the largest client of
``chip_smoke.py``'s Dirichlet world). For each local-epoch count ``e`` (the
first ``e`` epochs of one batch schedule) and each matmul precision it
reads, relative to the norm of the sequential update ``d``:

- ``gap``: the cohort engine (``CohortEngine.cohort_update``) vs the
  sequential oracle (``client.local_update``) from the same snapshot;
- ``self``: the sequential oracle vs itself started from the snapshot
  perturbed by one float32 rounding unit per element (``--ulps`` sets
  more; ``delta`` is its size relative to ``d``): how far the training
  dynamics amplify a rounding difference, with no second engine involved;
- ``self_by_step`` (long client, largest ``e`` only): the same distance
  after every local step, relative to ``d``.

Where the engines do the same arithmetic ``gap`` is zero. Where they round
differently (a TPU compiles the vmapped wave and the single-client step
into different programs), ``gap`` grows with the steps as ``self`` does
if the drift is amplified rounding. Writes ``engine_drift_<ulps>ulp.json``
to ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tree as tu
from repro.configs import get_config
from repro.data import ClientDataset, make_classification
from repro.federated import SimConfig
from repro.federated import client as client_lib
from repro.federated.simulator import _make_cohort_engine
from repro.models import model as model_lib

MODEL = "paper-cifar10-cnn"
SIZES = (751, 3470)
LR, SHUFFLE_SEED = 0.01, 7


def rel(a, b, ref) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(ref))


ULPS = 1     # perturbation size, in float32 rounding units (--ulps)


def perturbed(w0):
    """``w0`` moved by ``ULPS`` float32 rounding units per element, random
    sign."""
    sign = np.random.RandomState(1).choice([-1.0, 1.0], w0.shape[0])
    return w0 * (1.0 + np.float32(ULPS * 2.0 ** -23)
                 * jnp.asarray(sign, jnp.float32))


def measure(cfg, clients, params, epochs: int) -> list:
    spec = tu.FlatSpec(params)
    w0 = spec.flatten(params)
    engine = _make_cohort_engine(cfg, clients, spec, params,
                                 SimConfig(num_clients=len(clients),
                                           local_epochs=epochs))
    n = len(clients)
    deltas, _ = engine.cohort_update(jnp.stack([w0] * n), list(range(n)),
                                     [LR] * n, [SHUFFLE_SEED] * n)
    w1 = perturbed(w0)
    out = []
    for i, ds in enumerate(clients):
        kw = dict(epochs=epochs, batch_size=64, lr=LR, seed=SHUFFLE_SEED)
        d = spec.flatten(client_lib.local_update(params, cfg, ds, **kw)[0])
        # final models: w1 + d1 vs w0 + d
        end1 = spec.flatten(
            client_lib.local_update(spec.unflatten(w1), cfg, ds, **kw)[1])
        out.append({"samples": len(ds), "steps": epochs * (len(ds) // 64),
                    "update_over_w": float(jnp.linalg.norm(d)
                                           / jnp.linalg.norm(w0)),
                    "gap": rel(deltas[i], d, d),
                    "self": rel(end1, w0 + d, d),
                    "delta": rel(w1, w0, d)})
    return out


def self_by_step(cfg, ds, params, epochs: int) -> list:
    """``self`` after each local step: the oracle's own step function
    driven in lockstep from the snapshot and from its perturbed copy."""
    spec = tu.FlatSpec(params)
    w0 = spec.flatten(params)
    p1 = spec.unflatten(perturbed(w0))
    step = client_lib._get_step(cfg, 0.0, 0.0)
    lr = jnp.float32(LR)
    a, b = params, p1
    dist = []
    for batch in ds.epochs(epochs, 64, SHUFFLE_SEED):
        a, b = step(a, batch, params, lr), step(b, batch, p1, lr)
        dist.append(float(jnp.linalg.norm(spec.flatten(a) - spec.flatten(b))))
    d = float(jnp.linalg.norm(spec.flatten(a) - w0))
    return [x / d for x in dist]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--epochs", default="1,2,3,4,5")
    ap.add_argument("--precisions", default="default",
                    help="comma list of jax.default_matmul_precision values; "
                         "'default' leaves the backend's default")
    ap.add_argument("--ulps", type=int, default=1,
                    help="size of the self test's perturbation, in float32 "
                         "rounding units")
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    global ULPS
    ULPS = args.ulps

    cfg = get_config(MODEL)
    full = make_classification(sum(SIZES), cfg.num_classes,
                               image_hw=cfg.input_hw, seed=0, class_sep=0.7)
    bounds = np.cumsum((0,) + SIZES)
    clients = [ClientDataset(full.subset(np.arange(a, b)))
               for a, b in zip(bounds[:-1], bounds[1:])]
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "model": MODEL, "ulps": ULPS, "rows": []}
    t0 = time.perf_counter()
    epochs = [int(e) for e in args.epochs.split(",")]
    for prec in args.precisions.split(","):
        ctx = (contextlib.nullcontext if prec == "default" else
               functools.partial(jax.default_matmul_precision, prec))
        for e in epochs:
            with ctx():
                rows = measure(cfg, clients, params, e)
            for r in rows:
                r.update(precision=prec, epochs=e)
                print(json.dumps(r), flush=True)
            report["rows"] += rows
        with ctx():
            steps = self_by_step(cfg, clients[-1], params, max(epochs))
        print(json.dumps({"precision": prec, "self_by_step": steps}),
              flush=True)
        report.setdefault("self_by_step", {})[prec] = steps
    report["wall_s"] = time.perf_counter() - t0
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"engine_drift_{ULPS}ulp.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
