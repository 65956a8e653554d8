"""The plain reference: a client's local SGD, the FedPSA sensitivity sketch
and the FedPSA server, in straightforward ``jax.numpy``, over the model and
the trained leaves of the configuration's family module (``bench.families``:
its ``loss``, ``leaf_names`` and ``init_weights``).

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights`` (the benchmark's own, from the seed), the
data from the family's world, and from a run of the program only the order
in which client updates arrived (client id and the model version each was
dispatched with), which is the traffic the run served. Its arithmetic is
the paper's (FedPSA §4-5, Algorithm 1):

- a client trains ``local_epochs`` of SGD on its own samples, batches of
  ``min(batch, n)`` drawn by a fresh permutation per epoch, drop-last, at
  ``lr * decay**k`` for the k-th update the server receives;
- its sensitivity (Eq. 8) ``|g*theta - F*theta^2/2|`` on the shared
  calibration batch, with the empirical Fisher diagonal over four
  micro-batches, is compressed to k = 16 by a hashed Rademacher projection
  (Eq. 11; the hash is the published ``pcg`` mix, leaf by leaf);
- the server buffers 5 updates with kappa, the cosine of the client's
  sketch with the global model's; when full it applies the softmax of
  kappa over the thermometer temperature (Eq. 16-20), or the plain mean
  until the 50-long magnitude queue first fills, and sketches the new
  global model.

``dtype`` float32 runs every matrix product at ``Precision.HIGHEST``: the
reference. ``dtype`` bfloat16 runs every array in bfloat16: the control,
the precision a later change would be tempted to drop to.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import families, traffic as tr

SKETCH_K = 16
SKETCH_SEED = 42
FISHER_MICRO = 4
BUFFER = 5
QUEUE = 50
GAMMA = 5.0
DELTA = 0.5
SERVER_LR = 1.0
M32 = 0xFFFFFFFF


def leaf_sizes(config: dict) -> List[int]:
    """The sizes of the trained leaves, in the flat vector's order."""
    fam = families.load(config)
    shapes = fam.leaf_shapes(config)
    return [int(np.prod(shapes[n])) for n in fam.leaf_names(config)]


def init_weights(config: dict, seed: int) -> dict:
    """The run's initial weights (``tests/test_obs.py`` builds its engine
    from them)."""
    return families.load(config).init_weights(config, seed)


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# ---------------------------------------------------------------------------
# The client
# ---------------------------------------------------------------------------

class Client:
    """Local SGD for any client of one world, one compiled step."""

    def __init__(self, config: dict, traffic: dict, dtype=jnp.float32):
        self.config, self.traffic, self.dtype = config, traffic, dtype
        self.pad = int(traffic["batch"])
        fam = families.load(config)
        self.keys = fam.BATCH_KEYS

        @jax.jit
        def step(p, batch, wb, lr):
            g = jax.grad(fam.loss)(p, batch, wb, config, dtype)
            return jax.tree_util.tree_map(lambda a, b: a - lr.astype(a.dtype)
                                          * b, p, g)

        self._step = step

    def update(self, w0, x, y, lr: float, seed: int):
        """The client's trained weights from ``w0`` (a pytree)."""
        sched = tr.epoch_batch_indices(x.shape[0],
                                       int(self.traffic["local_epochs"]),
                                       self.pad, seed)
        p = jax.tree_util.tree_map(lambda a: a.astype(self.dtype), w0)
        bs = sched.shape[1]
        weight = np.zeros(self.pad, np.float32)
        weight[:bs] = 1.0
        rows = np.zeros(self.pad, np.int64)
        lr = jnp.asarray(lr, self.dtype)
        for idx in sched:
            rows[:bs] = idx
            batch = dict(zip(self.keys, (x[rows], y[rows].astype(np.int32))))
            p = self._step(p, batch, weight, lr)
        return p


# ---------------------------------------------------------------------------
# The sensitivity sketch
# ---------------------------------------------------------------------------

def _pcg(x):
    x = x.astype(jnp.uint32)
    state = x * jnp.uint32(747796405) + jnp.uint32(2891336453)
    word = (state >> ((state >> jnp.uint32(28)) + jnp.uint32(4))) ^ state
    word = word * jnp.uint32(277803737)
    return (word >> jnp.uint32(22)) ^ word


def _pcg_host(x: int) -> int:
    state = (x * 747796405 + 2891336453) & M32
    word = ((state >> (((state >> 28) + 4) & 31)) ^ state) & M32
    word = (word * 277803737) & M32
    return ((word >> 22) ^ word) & M32


def leaf_seed(seed: int, i: int) -> int:
    return _pcg_host((seed ^ ((i * 0x9E3779B9) & M32)) & M32)


def _project(vec, seed_u32: int, k: int):
    """(k,) projection of a flat leaf: sum_j vec_j * sign(r, j) / sqrt(k),
    with sign(r, j) from the hash of ``j * k + r`` under the leaf seed."""
    lin = jnp.arange(vec.shape[0], dtype=jnp.uint32)
    r = jnp.arange(k, dtype=jnp.uint32)
    h = _pcg(jnp.uint32(seed_u32) ^ _pcg(lin[:, None] * jnp.uint32(k) + r))
    sign = jnp.where((h >> jnp.uint32(31)) == 0, 1.0, -1.0).astype(vec.dtype)
    return jnp.sum(vec[:, None] * sign, axis=0) / np.sqrt(k).astype(vec.dtype)


def make_sketch(config: dict, calib: dict, dtype=jnp.float32):
    """params pytree -> (k,) f32 sensitivity sketch on the calibration
    batch, one compiled program."""
    fam = families.load(config)
    names = fam.leaf_names(config)
    cal = {k: jnp.asarray(v) for k, v in calib.items()}
    n = next(iter(cal.values())).shape[0]

    def loss(p, batch):
        rows = next(iter(batch.values())).shape[0]
        return fam.loss(p, batch, jnp.ones((rows,), jnp.float32), config,
                        dtype)

    @jax.jit
    def sketch(params):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        g = jax.grad(loss)(p, cal)
        m = n // FISHER_MICRO
        fisher = jax.tree_util.tree_map(jnp.zeros_like, p)
        for j in range(FISHER_MICRO):
            gj = jax.grad(loss)(p, {k: v[j * m:(j + 1) * m]
                                    for k, v in cal.items()})
            fisher = jax.tree_util.tree_map(lambda a, b: a + b * b, fisher, gj)
        total = jnp.zeros((SKETCH_K,), jnp.float32)
        for i, path in enumerate(names):
            th = _get(p, path).reshape(-1)
            gi = _get(g, path).reshape(-1)
            fi = _get(fisher, path).reshape(-1) / FISHER_MICRO
            s = jnp.abs(gi * th - 0.5 * fi * th * th)
            total = total + _project(s, leaf_seed(SKETCH_SEED, i),
                                     SKETCH_K).astype(jnp.float32)
        return total

    return sketch


def cosine(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    den = max(np.linalg.norm(a) * np.linalg.norm(b), 1e-12)
    return float(a @ b / den)


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------

def flatten(config: dict, params) -> np.ndarray:
    names = families.load(config).leaf_names(config)
    return np.concatenate([np.asarray(_get(params, path), np.float32)
                           .reshape(-1) for path in names])


def unflatten(config: dict, vec: np.ndarray) -> dict:
    fam = families.load(config)
    shapes = fam.leaf_shapes(config)
    out: dict = {}
    off = 0
    for path in fam.leaf_names(config):
        n = int(np.prod(shapes[path]))
        node = out
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.asarray(vec[off:off + n].reshape(shapes[path]))
        off += n
    return out


class Server:
    """FedPSA's server (Algorithm 1) on flat float64-free f32 vectors."""

    def __init__(self, w0: np.ndarray, sketch0: np.ndarray):
        self.w = np.asarray(w0, np.float32).copy()
        self.gs = np.asarray(sketch0, np.float64)
        self.buffer: List[np.ndarray] = []
        self.kappas: List[float] = []
        self.queue: List[float] = []
        self.pushes = 0
        self.m0 = 0.0

    def receive(self, delta: np.ndarray, sketch: np.ndarray) -> bool:
        """Buffer one update; True when it filled the buffer and the
        caller must aggregate (``aggregate``)."""
        self.kappas.append(cosine(sketch, self.gs))
        self.buffer.append(np.asarray(delta, np.float32))
        m = float(np.sum(np.square(delta, dtype=np.float64)))
        if len(self.queue) == QUEUE:
            self.queue[self.pushes % QUEUE] = m
        else:
            self.queue.append(m)
        self.pushes += 1
        if self.pushes == QUEUE:
            self.m0 = float(np.mean(self.queue))
        return len(self.buffer) == BUFFER

    def weights(self) -> np.ndarray:
        if self.pushes < QUEUE:
            return np.full(BUFFER, 1.0 / BUFFER)
        temp = max(np.mean(self.queue) / max(self.m0, 1e-30) * GAMMA + DELTA,
                   1e-6)
        z = np.asarray(self.kappas) / temp
        z = np.exp(z - z.max())
        return z / z.sum()

    def aggregate(self, sketch_fn) -> np.ndarray:
        """Apply the buffer; returns the kappas it was weighed with."""
        w = self.weights()
        step = np.zeros_like(self.w, np.float64)
        for wl, dl in zip(w, self.buffer):
            step += wl * dl
        self.w = (self.w + SERVER_LR * step).astype(np.float32)
        kappas = np.asarray(self.kappas)
        self.buffer, self.kappas = [], []
        self.gs = np.asarray(sketch_fn(self.w), np.float64)
        return kappas


def replay(config: dict, traffic: dict, world: tr.World, w0: dict,
           arrivals: Sequence[Tuple[int, int]], shuffle_seed: int,
           dtype=jnp.float32) -> dict:
    """The first ``len(arrivals)`` client updates and the aggregations they
    fill, from ``w0``. ``arrivals[k] = (client id, version dispatched
    with)`` for the k-th update received. Returns those ``versions``,
    flat f32 ``deltas`` (k, d), ``sketches`` (k, 16), ``globals`` (one (d,)
    per aggregation) and ``kappas`` (one (5,) per aggregation)."""
    client = Client(config, traffic, dtype)
    sk = make_sketch(config, world.calib, dtype)

    def sketch_flat(vec):
        return np.asarray(sk(unflatten(config, vec)))

    w_flat = flatten(config, w0)
    versions = [w_flat]
    init_sketch = sketch_flat(w_flat)
    server = Server(w_flat, init_sketch)
    deltas, sketches, globals_, kappas = [], [], [], []
    for k, (cid, version) in enumerate(arrivals):
        lr = traffic["lr"] * traffic["lr_decay"] ** k
        x, y = world.client(int(cid))
        snap = unflatten(config, versions[int(version)])
        p = client.update(snap, x, y, lr, shuffle_seed * 100003 + k)
        w_client = flatten(config, p)
        delta = w_client - versions[int(version)]
        s = np.asarray(sk(p))
        deltas.append(delta)
        sketches.append(s)
        if server.receive(delta, s):
            kappas.append(server.aggregate(sketch_flat))
            globals_.append(server.w.copy())
            versions.append(server.w.copy())
    return {"versions": [int(v) for _, v in arrivals],
            "deltas": np.stack(deltas), "sketches": np.stack(sketches),
            "globals": globals_, "kappas": kappas,
            "init_sketch": init_sketch}
