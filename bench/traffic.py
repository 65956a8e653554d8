"""The one traffic generator: a cell's federated world, made from the seed.

A traffic mix is a JSON file under ``bench/workloads/``; a configuration is
a JSON file under ``bench/configs/``. This module reads both and makes the
world a run trains on: every client's labelled images, the held-out test
set and the shared calibration batch. It imports nothing of the program.
The calibration batch is fixed (the server broadcasts one batch of noise
to every run), so the programs that close over it compile once and come
from the persistent compilation cache in every later run.

The generator is a copy of the program's synthetic stand-in for CIFAR-10
and MNIST (``repro.data.synthetic.make_classification``: a Gaussian mixture
with one mean per class and a mild class-dependent rotation, at
``class_sep=0.7`` as ``repro.launch.train.build_task`` uses) and of its
Dirichlet label-skew split (``repro.data.partition.dirichlet_partition``).
Two things differ, and neither changes the distribution:

- samples are drawn class by class, so the per-sample rotation is one
  (n_k, dim) x (dim, 8) product per class, not the (samples, dim, 8)
  gather of the original (4.9 GB at 50,000 CIFAR-sized samples);
- the split's structure (how many samples of each class each client holds)
  comes from the mix's fixed ``partition_seed``. The run's seed permutes
  which client holds which share and which class is which, and draws every
  sample, the weights and the batch shuffles. So every seed has the same
  set of client sizes, the same padded slab and the same compiled shapes,
  in another order.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# The program's generator constants (repro.data.synthetic, build_task).
CLASS_SEP = 0.7
NOISE = 1.0
ROTATION_DIMS = 8
TEST_FRACTION = 0.1
CALIB_BATCH = 64
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
    ``offsets[c]:offsets[c + 1]``."""
    x_train: np.ndarray      # (n_train, H, W, C) float32
    y_train: np.ndarray      # (n_train,) int64
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


class _Mixture:
    """The class structure: one mean per class on a sphere of radius
    ``CLASS_SEP`` and a (dim, 8) rotation per class."""

    def __init__(self, rng: np.random.Generator, num_classes: int, dim: int):
        means = rng.standard_normal((num_classes, dim), np.float32)
        self.means = means * (CLASS_SEP / np.linalg.norm(
            means, axis=1, keepdims=True))
        self.rot = (rng.standard_normal((num_classes, dim, ROTATION_DIMS),
                                        np.float32) / np.sqrt(dim))

    def fill(self, rng: np.random.Generator, out: np.ndarray,
             labels: np.ndarray) -> None:
        """Draw one sample per label into the rows of ``out`` (n, dim)."""
        for k in np.unique(labels):
            rows = np.nonzero(labels == k)[0]
            x = rng.standard_normal((rows.size, out.shape[1]), np.float32)
            x *= np.float32(0.3 * NOISE)
            x += self.means[k]
            x[:, :ROTATION_DIMS] += 0.5 * np.tanh(x @ self.rot[k])
            out[rows] = x


def make_world(config: dict, traffic: dict, seed: int) -> World:
    """The world of one run: the mix's split structure, permuted and filled
    with samples drawn from ``seed``."""
    hw = tuple(config["input_hw"])
    dim = int(np.prod(hw))
    K = int(config["num_classes"])
    C = int(traffic["clients"])
    n_total = int(traffic["samples"])
    n_test = int(n_total * TEST_FRACTION)
    n_train = n_total - n_test
    counts = client_class_counts(n_train, K, C, float(traffic["alpha"]),
                                 int(traffic["partition_seed"]))
    s = sub_seeds(seed)
    rng = np.random.default_rng(s["data"])
    counts = counts[rng.permutation(C)][:, rng.permutation(K)]
    sizes = counts.sum(axis=1)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    # client-major labels, each client's samples grouped by class
    y_train = np.repeat(np.tile(np.arange(K), C), counts.reshape(-1))
    mix = _Mixture(rng, K, dim)
    x_train = np.empty((n_train, dim), np.float32)
    mix.fill(rng, x_train, y_train)
    y_test = rng.integers(0, K, n_test)
    x_test = np.empty((n_test, dim), np.float32)
    mix.fill(rng, x_test, y_test)
    # the shared calibration batch is the protocol's, not the seed's:
    # Gaussian noise with uniform labels from a fixed RandomState(123), as
    # repro.data.calibration draws it (FedPSA Table 5)
    crng = np.random.RandomState(CALIB_SEED)
    calib = {"x": crng.randn(CALIB_BATCH, *hw).astype(np.float32),
             "y": crng.randint(0, K, size=CALIB_BATCH).astype(np.int32)}
    return World(x_train=x_train.reshape((n_train,) + hw),
                 y_train=y_train.astype(np.int64), offsets=offsets,
                 x_test=x_test.reshape((n_test,) + hw),
                 y_test=y_test.astype(np.int64), calib=calib, num_classes=K)


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
