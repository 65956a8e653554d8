"""The paper's CNNs (FedPSA section 6.1): CIFAR-10 and MNIST.

The plain reference of the model, its world and its operation count:

- the model: 5x5 SAME convs with bias and ReLU, each followed by a 2x2
  max-pool, then a stack of fully connected layers with ReLU between them;
  truncated-normal fan-in initial weights and zero biases;
- the world: the program's synthetic stand-in for CIFAR-10 and MNIST
  (``repro.data.synthetic.make_classification``: a Gaussian mixture with one
  mean per class and a mild class-dependent rotation, at ``class_sep=0.7`` as
  ``repro.launch.train.build_task`` uses) over the Dirichlet label-skew split
  of ``bench.traffic.client_class_counts``. Samples are drawn class by class,
  so the per-sample rotation is one (n_k, dim) x (dim, 8) product per class,
  not the (samples, dim, 8) gather of the original (4.9 GB at 50,000
  CIFAR-sized samples); the distribution is the same. The shared calibration
  batch is the protocol's, not the seed's: Gaussian noise with uniform labels
  from a fixed ``RandomState(123)``, as ``repro.data.calibration`` draws it
  (FedPSA Table 5);
- the operations: ``forward_flops`` counts the multiply-adds of the convs
  and dense layers, two operations each, for one sample. A SAME conv's taps
  that fall on its zero padding are not counted (they add nothing), and
  neither are bias adds, ReLUs and pools. A training step is three forward
  passes' worth (the forward and the two products of the backward pass).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as tr
from bench.traffic import program_inputs  # noqa: F401

FIELDS = ("family", "input_hw", "cnn_channels", "cnn_kernel", "mlp_hidden",
          "num_classes", "param_dtype")
BATCH_KEYS = ("x", "y")

# The program's generator constants (repro.data.synthetic, build_task).
CLASS_SEP = 0.7
NOISE = 1.0
ROTATION_DIMS = 8
CALIB_BATCH = 64


def leaf_names(config: dict) -> List[Tuple[str, str]]:
    """(layer, w|b) in the order the flat parameter vector lays them out
    (sorted layer names, bias before weight)."""
    layers = [f"conv{i}" for i in range(len(config["cnn_channels"]))]
    layers += [f"fc{i}" for i in range(len(config["mlp_hidden"]) + 1)]
    return [(l, p) for l in sorted(layers) for p in ("b", "w")]


def leaf_shapes(config: dict) -> Dict[Tuple[str, str], tuple]:
    H, W, C = config["input_hw"]
    k = config["cnn_kernel"]
    shapes = {}
    cin = C
    for i, ch in enumerate(config["cnn_channels"]):
        shapes[(f"conv{i}", "w")] = (k, k, cin, ch)
        shapes[(f"conv{i}", "b")] = (ch,)
        cin = ch
        H, W = H // 2, W // 2
    dims = [H * W * cin] + list(config["mlp_hidden"]) + [config["num_classes"]]
    for i in range(len(dims) - 1):
        shapes[(f"fc{i}", "w")] = (dims[i], dims[i + 1])
        shapes[(f"fc{i}", "b")] = (dims[i + 1],)
    return shapes


def params(config: dict) -> int:
    return int(sum(np.prod(s) for s in leaf_shapes(config).values()))


def init_weights(config: dict, seed: int) -> dict:
    """The run's initial weights, made on the device in one jitted call:
    truncated-normal fan-in weights (convs at 1/sqrt(k*k*c_in)) and zero
    biases, in float32, the type the configuration trains in."""
    shapes = leaf_shapes(config)
    names = [n for n in leaf_names(config) if n[1] == "w"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for kk, (layer, _) in zip(keys, names):
            shp = shapes[(layer, "w")]
            fan_in = (int(np.prod(shp[:3])) if layer.startswith("conv")
                      else shp[0])
            w = jax.random.truncated_normal(kk, -2.0, 2.0, shp, jnp.float32)
            out[layer] = {"w": w / np.sqrt(fan_in),
                          "b": jnp.zeros(shapes[(layer, "b")], jnp.float32)}
        return out

    return make(jax.random.PRNGKey(tr.sub_seeds(seed)["model"]))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def forward(params, x, config: dict, dtype=jnp.float32):
    """(B, H, W, C) images -> (B, classes) logits."""
    prec = _precision(dtype)
    x = x.astype(dtype)
    for i in range(len(config["cnn_channels"])):
        p = params[f"conv{i}"]
        x = jax.lax.conv_general_dilated(
            x, p["w"].astype(dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
        x = jax.nn.relu(x + p["b"].astype(dtype))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    n_fc = len(config["mlp_hidden"]) + 1
    for i in range(n_fc):
        p = params[f"fc{i}"]
        x = jnp.dot(x, p["w"].astype(dtype), precision=prec) \
            + p["b"].astype(dtype)
        if i < n_fc - 1:
            x = jax.nn.relu(x)
    return x


def loss(params, batch, weight, config: dict, dtype=jnp.float32):
    """Cross-entropy summed over the rows with ``weight`` 1, over their
    count: the mean over the real samples of a padded batch."""
    logits = forward(params, batch["x"], config, dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    w = weight.astype(dtype)
    return jnp.sum((lse - gold) * w) / jnp.maximum(jnp.sum(w), 1)


# ---------------------------------------------------------------------------
# The world
# ---------------------------------------------------------------------------

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


def make_world(config: dict, traffic: dict, seed: int) -> tr.World:
    """The mix's split structure, its clients in the split's order, classes
    permuted and samples drawn from ``seed``.

    The data stream's first draw is a permutation of the clients that
    nothing uses. Drawing it keeps each seed's classes and samples those of
    the world that puts client i on share ``perm[i]``, class by class
    (``bench/test_families.py`` compares the two)."""
    hw = tuple(config["input_hw"])
    dim = int(np.prod(hw))
    K = int(config["num_classes"])
    C = int(traffic["clients"])
    n_total = int(traffic["samples"])
    n_test = int(n_total * tr.TEST_FRACTION)
    n_train = n_total - n_test
    counts = tr.client_class_counts(n_train, K, C, float(traffic["alpha"]),
                                    int(traffic["partition_seed"]))
    s = tr.sub_seeds(seed)
    rng = np.random.default_rng(s["data"])
    rng.permutation(C)
    counts = counts[:, rng.permutation(K)]
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
    crng = np.random.RandomState(tr.CALIB_SEED)
    calib = {"x": crng.randn(CALIB_BATCH, *hw).astype(np.float32),
             "y": crng.randint(0, K, size=CALIB_BATCH).astype(np.int32)}
    return tr.World(x_train=x_train.reshape((n_train,) + hw),
                    y_train=y_train.astype(np.int64), offsets=offsets,
                    x_test=x_test.reshape((n_test,) + hw),
                    y_test=y_test.astype(np.int64), calib=calib,
                    num_classes=K)


# ---------------------------------------------------------------------------
# Operations
# ---------------------------------------------------------------------------

def _taps(n: int, k: int) -> int:
    """Kernel taps inside an n-long input, summed over a stride-1 SAME
    conv's n outputs."""
    pad = (k - 1) // 2
    return sum(min(o - pad + k, n) - max(o - pad, 0) for o in range(n))


def forward_flops(config: dict, traffic: dict = None) -> float:
    H, W, C = config["input_hw"]
    k = int(config["cnn_kernel"])
    flops = 0
    cin = C
    for ch in config["cnn_channels"]:
        flops += 2 * _taps(H, k) * _taps(W, k) * cin * ch
        cin = ch
        H, W = H // 2, W // 2                      # 2x2 max-pool
    dims = [H * W * cin] + list(config["mlp_hidden"]) + [config["num_classes"]]
    flops += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return float(flops)


def train_flops(config: dict, traffic: dict, counters: dict) -> float:
    """Operations of SGD over the window's real samples of real steps."""
    return 3.0 * forward_flops(config) * counters["samples"]
