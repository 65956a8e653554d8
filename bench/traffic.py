"""The one traffic generator: what every family's world shares.

A traffic mix is a JSON file under ``bench/workloads/``; a configuration is
a JSON file under ``bench/configs/``. The configuration's family module
(``bench.families``) makes the world a run trains on from both: every
client's samples, the held-out test set and the shared calibration batch,
in a ``World``. This module holds what does not depend on the model, and
imports nothing of the program outside ``program_inputs``:

- the run's independent seed streams (``sub_seeds``);
- the split's structure, drawn from the mix's fixed ``partition_seed``
  (``client_class_counts``, a copy of the program's Dirichlet label-skew
  split ``repro.data.partition.dirichlet_partition``; token families deal
  documents, ``bench.token_world``). Client c holds the split's share c on
  every seed, so every seed has the same client sizes, the same padded
  slab, the same waves on the mix's fixed timeline and the same compiled
  shapes; the seed draws every sample, the weights and the batch
  shuffles;
- a client's batch schedule (``epoch_batch_indices``);
- the program's client datasets over a world (``program_inputs``).

The calibration batch is fixed (the server broadcasts one batch to every
run), so the programs that close over it compile once and come from the
persistent compilation cache in every later run.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# The program's constants (repro.launch.train.build_task,
# repro.data.calibration).
TEST_FRACTION = 0.1
CALIB_SEED = 123
MIN_CLIENT_SIZE = 2


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json`` — a configuration, a traffic mix or a
    cell's limits, found by its name."""
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def sub_seeds(seed: int) -> Dict[str, int]:
    """Independent streams from one run seed (any size, 64-bit and up).

    ``shuffle`` feeds the simulator's ``SimConfig.seed``, which the program
    turns into per-dispatch batch-shuffle seeds ``seed * 100003 + k`` for
    ``numpy.random.RandomState``; those must stay below 2**32, so it is
    kept under 40,000."""
    st = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
    return {"data": int(st[0]), "model": int(st[1]),
            "shuffle": int(st[2]) % 40_000}


def client_class_counts(n_train: int, num_classes: int, num_clients: int,
                        alpha: float, partition_seed: int) -> np.ndarray:
    """(clients, classes) sample counts of the split, from the fixed seed.

    ``alpha > 0``: Dirichlet(alpha) label skew, as
    ``dirichlet_partition`` draws it (per class, client proportions from
    Dir(alpha), cut by the cumulative sum; retried until every client holds
    at least ``MIN_CLIENT_SIZE``). ``alpha <= 0``: the IID split, as
    ``iid_partition`` (a random permutation cut into near-equal parts)."""
    rng = np.random.RandomState(partition_seed)
    class_n = np.full(num_classes, n_train // num_classes)
    class_n[: n_train % num_classes] += 1
    if alpha <= 0:
        labels = np.repeat(np.arange(num_classes), class_n)
        rng.shuffle(labels)
        counts = np.zeros((num_clients, num_classes), np.int64)
        for c, part in enumerate(np.array_split(labels, num_clients)):
            counts[c] = np.bincount(part, minlength=num_classes)
        return counts
    for _ in range(100):
        counts = np.zeros((num_clients, num_classes), np.int64)
        for k in range(num_classes):
            p = rng.dirichlet(np.full(num_clients, alpha))
            cuts = (np.cumsum(p) * class_n[k]).astype(int)[:-1]
            counts[:, k] = np.diff(np.concatenate([[0], cuts, [class_n[k]]]))
        if counts.sum(axis=1).min() >= MIN_CLIENT_SIZE:
            return counts
    raise RuntimeError("no Dirichlet split gave every client "
                       f"{MIN_CLIENT_SIZE} samples")


@dataclass
class World:
    """A cell's data. ``x_train`` is client-major: client c holds rows
    ``offsets[c]:offsets[c + 1]``. ``calib`` is keyed by the family's
    ``BATCH_KEYS``."""
    x_train: np.ndarray      # (n_train, ...) features or token sequences
    y_train: np.ndarray      # (n_train, ...) labels
    offsets: np.ndarray      # (clients + 1,) int64
    x_test: np.ndarray
    y_test: np.ndarray
    calib: Dict[str, np.ndarray]
    num_classes: int

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    def client(self, c: int):
        lo, hi = self.offsets[c], self.offsets[c + 1]
        return self.x_train[lo:hi], self.y_train[lo:hi]


def make_world(config: dict, traffic: dict, seed: int) -> World:
    """The world of one run, made by the configuration's family module."""
    from bench import families
    return families.load(config).make_world(config, traffic, seed)


def program_inputs(world: World):
    """The program's client datasets and test set over a world's arrays,
    one ``ClientDataset`` per client; the only place the benchmark's data
    side imports the program."""
    from repro.data.loader import ClientDataset
    from repro.data.synthetic import SyntheticClassification
    K = world.num_classes
    clients = [ClientDataset(SyntheticClassification(*world.client(c), K))
               for c in range(len(world.sizes))]
    test = SyntheticClassification(world.x_test, world.y_test, K)
    return clients, test


def epoch_batch_indices(n: int, num_epochs: int, batch_size: int,
                        seed: int) -> np.ndarray:
    """One client's batch schedule: ``(steps, bs)`` indices, ``bs =
    min(batch_size, n)``, drop-last, a fresh permutation per epoch from
    ``RandomState(seed)`` (a copy of ``repro.data.loader``'s rule, which
    is how a client orders its local data)."""
    rng = np.random.RandomState(seed)
    bs = min(batch_size, n)
    m = n // bs
    out = np.empty((num_epochs * m, bs), np.int32)
    for e in range(num_epochs):
        out[e * m:(e + 1) * m] = rng.permutation(n)[:m * bs].reshape(m, bs)
    return out
