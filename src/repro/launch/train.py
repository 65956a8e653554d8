"""Federated training driver (the paper's experiment runner).

    PYTHONPATH=src python -m repro.launch.train \
        --alg fedpsa --model paper-synthetic-mlp --alpha 0.1 \
        --clients 50 --horizon 86400 --out artifacts/runs

Runs one (algorithm x Dirichlet-alpha x latency setting) cell of the paper's
tables on the synthetic stand-in datasets and writes the learning curve +
summary JSON. ``--arch`` accepts any architecture id whose family is in the
model-family registry: cnn/mlp train the paper's classification worlds,
token families (dense/ssm/moe/hybrid — e.g. ``--arch fed-lm-smoke``, or any
assigned arch's ``-smoke`` reduction) train the federated LM fine-tuning
scenario on a document-partitioned synthetic corpus. The full-scale configs
are exercised by the dry-run, not by CPU training.

``--sweep seeds=0,1,2`` (or ``--sweep alpha=0.3,0.6,0.9`` etc.) runs the
variants as lanes of ONE batched simulation over a shared event timeline
(``run_sweep``), printing per-lane and mean±std accuracy.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import PSAConfig
from repro.data import (ClientDataset, dirichlet_partition,
                        document_partition, iid_partition,
                        make_calibration_batch, make_classification,
                        make_lm_corpus, train_test_split)
from repro.data.synthetic import SyntheticClassification
from repro.federated import (SimConfig, SweepConfig, run_algorithm,
                             run_sweep, ALGORITHMS)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.models import registry


def build_lm_task(cfg, num_samples: int, alpha: float, num_clients: int,
                  seed: int, calib_source: str = "gaussian",
                  seq_len: int = 32):
    """The federated LM fine-tuning world: a synthetic bigram corpus,
    document-partitioned across clients (Dirichlet-skewed shard sizes when
    ``alpha > 0``), chopped into ``(n_i, seq_len)`` token sequences; the
    held-out HEAD of the corpus (its first ``n_test`` sequences) is the
    next-token-accuracy test set and the remainder is partitioned for
    training. ``num_samples`` counts sequences across train + test."""
    n_test = max(2, num_samples // 10)
    doc_len = 4 * seq_len
    corpus = make_lm_corpus((num_samples - n_test) * seq_len + doc_len
                            + n_test * seq_len,
                            vocab=cfg.vocab_size, seed=seed)
    test_toks = corpus[:n_test * seq_len].reshape(n_test, seq_len)
    test = SyntheticClassification(x=test_toks, y=test_toks,
                                   num_classes=cfg.vocab_size)
    parts = document_partition(corpus[n_test * seq_len:], num_clients,
                               seq_len, doc_len=doc_len, alpha=alpha,
                               seed=seed)
    clients = [ClientDataset(SyntheticClassification(x=p, y=p,
                                                     num_classes=cfg.vocab_size))
               for p in parts]
    calib = make_calibration_batch(test, 8, calib_source)
    return cfg, clients, test, calib


def build_task(model_name: str, num_samples: int, alpha: float, num_clients: int,
               seed: int, calib_source: str = "gaussian", seq_len: int = 32):
    cfg = get_config(model_name)
    if cfg.family == "cnn":
        hw = cfg.input_hw
        full = make_classification(num_samples, cfg.num_classes,
                                   image_hw=hw, seed=seed, class_sep=0.7)
    elif cfg.family == "mlp":
        full = make_classification(num_samples, cfg.num_classes,
                                   dim=cfg.input_hw[0], seed=seed, class_sep=0.7)
    elif (registry.is_registered(cfg.family)
          and registry.get_family(cfg).data_kind == "tokens"):
        return build_lm_task(cfg, num_samples, alpha, num_clients, seed,
                             calib_source, seq_len)
    else:
        raise ValueError(
            f"{model_name}: family {cfg.family!r} has no federated data "
            f"path (registered families train via the registry; audio/vlm "
            f"archs are exercised via the dry-run)")
    train, test = train_test_split(full, 0.1)
    if alpha <= 0:
        parts = iid_partition(train, num_clients, seed)
    else:
        parts = dirichlet_partition(train, num_clients, alpha, seed)
    clients = [ClientDataset(train.subset(ix)) for ix in parts]
    calib = make_calibration_batch(train, 64, calib_source)
    return cfg, clients, test, calib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--alg", default="fedpsa", choices=ALGORITHMS)
    ap.add_argument("--arch", "--model", dest="model",
                    default="paper-synthetic-mlp",
                    help="architecture registry id; any family in the "
                         "model-family registry trains (token families get "
                         "the federated LM scenario, e.g. fed-lm-smoke)")
    ap.add_argument("--alpha", type=float, default=0.1,
                    help="Dirichlet alpha; <=0 for IID")
    ap.add_argument("--clients", type=int, default=50)
    ap.add_argument("--concurrency", type=float, default=0.2)
    ap.add_argument("--horizon", type=float, default=86_400)
    ap.add_argument("--samples", type=int, default=10_000,
                    help="total samples (image) or sequences (token tasks)")
    ap.add_argument("--seq", type=int, default=32,
                    help="sequence length for token (LM) tasks")
    ap.add_argument("--engine", default="cohort",
                    choices=["cohort", "sequential"])
    ap.add_argument("--latency", default="uniform",
                    choices=["uniform", "longtail", "lognormal"])
    ap.add_argument("--lat-lo", type=float, default=10)
    ap.add_argument("--lat-hi", type=float, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calib", default="gaussian", choices=["gaussian", "real"])
    ap.add_argument("--buffer", type=int, default=5)
    ap.add_argument("--queue", type=int, default=50)
    ap.add_argument("--gamma", type=float, default=5.0)
    ap.add_argument("--delta", type=float, default=0.5)
    ap.add_argument("--sketch-k", type=int, default=16)
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard the policy server (and train waves "
                         "data-parallel) over an N-device mesh; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--sweep", default=None, metavar="SPEC",
                    help="run S variants as ONE batched simulation "
                         "(run_sweep; lanes share the event timeline). "
                         "SPEC is either 'seeds=0,1,2' (per-lane model+"
                         "shuffle seeds) or a policy hyperparameter grid "
                         "like 'alpha=0.3,0.6,0.9' or "
                         "'gamma=0.1,1,5' (PolicyParams field names)")
    ap.add_argument("--out", default="artifacts/runs")
    args = ap.parse_args()

    enable_compile_cache()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_fed_mesh
        mesh = make_fed_mesh(args.mesh)
    cfg, clients, test, calib = build_task(
        args.model, args.samples, args.alpha, args.clients, args.seed,
        args.calib, seq_len=args.seq)
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)
    sim = SimConfig(num_clients=args.clients, concurrency=args.concurrency,
                    horizon=args.horizon, latency_kind=args.latency,
                    latency_lo=args.lat_lo, latency_hi=args.lat_hi,
                    seed=args.seed, engine=args.engine, mesh=mesh)
    psa = PSAConfig(buffer_size=args.buffer, queue_len=args.queue,
                    gamma=args.gamma, delta=args.delta, sketch_k=args.sketch_k)
    os.makedirs(args.out, exist_ok=True)
    name = f"{args.alg}_{args.model}_a{args.alpha}_{args.latency}{int(args.lat_hi)}_s{args.seed}"
    if args.mesh:
        name += f"_mesh{args.mesh}"

    if args.sweep:
        key, _, vals = args.sweep.partition("=")
        if not vals:
            raise SystemExit("--sweep wants 'seeds=...' or '<hyper>=v1,v2'")
        if key == "seeds":
            seeds = [int(v) for v in vals.split(",")]
            sweep = SweepConfig(model_seeds=seeds, data_seeds=seeds)
            lane_tags = [f"seed{s}" for s in seeds]
        else:
            grid = [float(v) for v in vals.split(",")]
            sweep = SweepConfig(policy_params=[{key: v} for v in grid])
            lane_tags = [f"{key}{v:g}" for v in grid]
        t0 = time.time()
        res = run_sweep(args.alg, cfg, params, clients, test, sim, sweep,
                        psa_cfg=psa, calib_batch=calib)
        wall = time.time() - t0
        mean, std = res.accuracy_mean_std()
        rec = {
            "alg": args.alg, "model": args.model, "alpha": args.alpha,
            "latency": [args.latency, args.lat_lo, args.lat_hi],
            "sweep": args.sweep, "lanes": lane_tags,
            "final_accuracy": res.final_accuracy, "aulc": res.aulc,
            "final_accuracy_mean": mean, "final_accuracy_std": std,
            "versions": res.versions, "dispatches": res.dispatches,
            "times": res.times, "lane_accuracies": res.lane_accuracies,
            "wall_s": round(wall, 1), "engine": res.engine,
        }
        name += f"_sweep-{key}{len(lane_tags)}"
        path = os.path.join(args.out, name + ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        for tag, acc in zip(lane_tags, res.final_accuracy):
            print(f"[train]   lane {tag}: final={acc:.4f}")
        print(f"[train] {name}: mean={mean:.4f}±{std:.4f} ({wall:.0f}s, "
              f"one batched simulation) -> {path}")
        return

    t0 = time.time()
    res = run_algorithm(args.alg, cfg, params, clients, test, sim,
                        psa_cfg=psa, calib_batch=calib)
    wall = time.time() - t0
    rec = {
        "alg": args.alg, "model": args.model, "alpha": args.alpha,
        "latency": [args.latency, args.lat_lo, args.lat_hi],
        "final_accuracy": res.final_accuracy, "aulc": res.aulc,
        "versions": res.versions, "dispatches": res.dispatches,
        "times": res.times, "accuracies": res.accuracies,
        "wall_s": round(wall, 1), "mesh_devices": args.mesh or None,
        "engine": res.engine,
    }
    path = os.path.join(args.out, name + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[train] {name}: final={res.final_accuracy:.4f} aulc={res.aulc:.4f} "
          f"({wall:.0f}s) -> {path}")


if __name__ == "__main__":
    main()
