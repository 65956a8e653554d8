"""Grouped member-GEMM kernel + member_dot routing seam.

Three layers of contract, mirroring how the kernel is reached in production:

1. ``grouped_matmul_pallas`` vs the einsum oracle (``kernels/ref.py``) over
   ragged bucket shapes — G=1, non-power-of-2 everything, fully padded rows
   via the valid mask — in interpret mode (compiled mode only exists on TPU).
2. ``member_dot`` routing: both modes must agree through every composition
   the cohort engines actually build — vmap(grad), the sweep lane vmap on
   top, ncon=2 contractions, shared (unbatched) weights.
3. The cohort engines end to end: ``member_kernel="grouped"`` must match the
   default vmap path within the 1e-5 golden gate on real cohort updates.

Tolerances on the kernel are *relative*: with K padded to multiple 128-blocks
the f32 accumulation order differs from a single einsum reduction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tree as tu
from repro.configs import get_config
from repro.data import (ClientDataset, StackedClients, dirichlet_partition,
                        make_classification, train_test_split)
from repro.federated.cohort import CohortEngine
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.ref import grouped_matmul_ref
from repro.models import member_math
from repro.models import model as M


def _rel_close(got, want, tol=1e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    err = float(jnp.max(jnp.abs(got - want))) / scale
    assert err < tol, err


@pytest.mark.parametrize("G,Mm,K,N", [
    (1, 8, 16, 16),        # single-member bucket
    (3, 130, 200, 96),     # non-power-of-2 on every axis, K > one block
    (5, 1, 7, 3),          # tiny ragged odds
    (4, 32, 256, 64),      # K spans two 128-blocks exactly
])
def test_kernel_vs_ref(G, Mm, K, N):
    key = jax.random.PRNGKey(G * 1000 + K)
    lhs = jax.random.normal(key, (G, Mm, K), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (G, K, N), jnp.float32)
    out = grouped_matmul_pallas(lhs, rhs, interpret=True)
    _rel_close(out, grouped_matmul_ref(lhs, rhs))


def test_kernel_padded_rows_are_exact_noops():
    """valid=0 groups must come back exactly zero, not approximately."""
    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, (4, 16, 64), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, 32), jnp.float32)
    valid = jnp.array([1.0, 0.0, 1.0, 0.0])
    out = grouped_matmul_pallas(lhs, rhs, valid=valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)
    _rel_close(out[0], grouped_matmul_ref(lhs, rhs)[0])
    _rel_close(out[2], grouped_matmul_ref(lhs, rhs)[2])


def test_kernel_dtype_promotion():
    key = jax.random.PRNGKey(3)
    lhs = jax.random.normal(key, (2, 8, 16), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 8), jnp.float32)
    out = grouped_matmul_pallas(lhs, rhs, interpret=True)
    assert out.dtype == jnp.float32
    _rel_close(out, grouped_matmul_ref(lhs, rhs), tol=5e-3)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs a TPU backend")
def test_kernel_compiled_matches_interpret():
    key = jax.random.PRNGKey(5)
    lhs = jax.random.normal(key, (3, 64, 192), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (3, 192, 64), jnp.float32)
    # the kernel follows the caller's matmul precision, as the interpreter's
    # XLA dot does: under f32 both sides contract in f32
    with jax.default_matmul_precision("float32"):
        a = grouped_matmul_pallas(lhs, rhs, interpret=False)
        b = grouped_matmul_pallas(lhs, rhs, interpret=True)
    _rel_close(a, b)


# --- member_dot routing ---------------------------------------------------

def _both_modes(fn, *args):
    with member_math.routing("vmap"):
        a = fn(*args)
    with member_math.routing("grouped"):
        b = fn(*args)
    return a, b


def test_member_dot_grad_under_member_vmap():
    """The composition the cohort engines build: grad inside, vmap outside."""
    key = jax.random.PRNGKey(0)
    B, Mm, K, N = 4, 6, 24, 8
    x = jax.random.normal(key, (B, Mm, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (B, K, N))

    def loss(w1, x1):
        return jnp.sum(jnp.tanh(member_math.member_dot(x1, w1)) ** 2)

    f = jax.jit(jax.vmap(jax.value_and_grad(loss)))
    (la, ga), (lb, gb) = _both_modes(f, w, x)
    _rel_close(la, lb)
    _rel_close(ga, gb)


def test_member_dot_under_lane_vmap():
    """Sweep lanes fold into the group axis (vmap over vmap)."""
    key = jax.random.PRNGKey(1)
    L, B, Mm, K, N = 3, 4, 5, 16, 8
    x = jax.random.normal(key, (B, Mm, K))           # shared data across lanes
    w = jax.random.normal(jax.random.fold_in(key, 1), (L, B, K, N))
    f = jax.jit(jax.vmap(jax.vmap(member_math.member_dot),
                         in_axes=(None, 0)))
    a, b = _both_modes(f, x, w)
    _rel_close(a, b)


def test_member_dot_ncon2():
    """The attention output projection contracts two axes (heads, head_dim)."""
    key = jax.random.PRNGKey(2)
    B, S, H, D, O = 3, 5, 4, 8, 16
    x = jax.random.normal(key, (B, S, H, D))
    w = jax.random.normal(jax.random.fold_in(key, 1), (B, H, D, O))
    f = jax.jit(jax.vmap(lambda x1, w1: member_math.member_dot(x1, w1, ncon=2)))
    a, b = _both_modes(f, x, w)
    _rel_close(a, b)


def test_member_dot_shared_weights():
    """Weights not batched (wd=None): one big dot, no broadcast copies."""
    key = jax.random.PRNGKey(4)
    B, Mm, K, N = 5, 3, 12, 7
    x = jax.random.normal(key, (B, Mm, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N))
    f = jax.jit(jax.vmap(member_math.member_dot, in_axes=(0, None)))
    a, b = _both_modes(f, x, w)
    _rel_close(a, b)


def test_member_dot_unbatched_fallback():
    """Outside any vmap the grouped mode still works (plain 2-D bind)."""
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (9, 13))
    w = jax.random.normal(jax.random.fold_in(key, 1), (13, 5))
    a, b = _both_modes(member_math.member_dot, x, w)
    _rel_close(a, b)


def test_routing_validates_and_restores():
    assert member_math.current_mode() == "vmap"
    with pytest.raises(ValueError):
        with member_math.routing("nope"):
            pass
    with member_math.routing("grouped"):
        assert member_math.current_mode() == "grouped"
    assert member_math.current_mode() == "vmap"


# --- cohort engines end to end --------------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = get_config("paper-synthetic-mlp")
    full = make_classification(3_000, 10, 32, seed=0, class_sep=0.7)
    train, _ = train_test_split(full, 0.1)
    parts = dirichlet_partition(train, 6, alpha=0.3, seed=0)
    datasets = [ClientDataset(train.subset(ix)) for ix in parts]
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, datasets, params


def _engine(cfg, params, datasets, member_kernel):
    spec = tu.FlatSpec(params)
    stacked = StackedClients.from_datasets(datasets)
    eng = CohortEngine(cfg, stacked, spec, params, local_epochs=2,
                       batch_size=32, member_kernel=member_kernel)
    return spec, eng


def test_cohort_grouped_matches_vmap(world):
    """The 1e-5 acceptance gate: grouped member math on a real cohort
    update pins to the default vmap path."""
    cfg, datasets, params = world
    spec, eng_v = _engine(cfg, params, datasets, "vmap")
    _, eng_g = _engine(cfg, params, datasets, "grouped")
    flat = jnp.array(spec.flatten(params), copy=True)
    cids, lrs, seeds = [0, 2, 5], [0.01, 0.008, 0.012], [11, 22, 33]
    thetas = jnp.stack([flat] * 3)
    dv, wv = eng_v.cohort_update(thetas, cids, lrs, seeds)
    dg, wg = eng_g.cohort_update(thetas, cids, lrs, seeds)
    assert float(jnp.max(jnp.abs(dv - dg))) <= 1e-5
    assert float(jnp.max(jnp.abs(wv - wg))) <= 1e-5


def test_sweep_grouped_matches_vmap(world):
    """Same gate one vmap deeper: the S-lane sweep folds lanes into the
    grouped kernel's group axis and must still pin to the vmap path."""
    cfg, datasets, params = world
    spec, eng_v = _engine(cfg, params, datasets, "vmap")
    _, eng_g = _engine(cfg, params, datasets, "grouped")
    flat = jnp.array(spec.flatten(params), copy=True)
    S, cids, lrs = 2, [0, 3], [0.01, 0.009]
    thetas = jnp.stack([jnp.stack([flat] * len(cids))] * S)
    seeds = np.array([[7, 8], [9, 10]])
    dv, wv = eng_v.sweep_update(thetas, cids, lrs, seeds)
    dg, wg = eng_g.sweep_update(thetas, cids, lrs, seeds)
    assert float(jnp.max(jnp.abs(dv - dg))) <= 1e-5
    assert float(jnp.max(jnp.abs(wv - wg))) <= 1e-5


def test_cohort_rejects_unknown_member_kernel(world):
    cfg, datasets, params = world
    with pytest.raises(ValueError, match="member_kernel"):
        _engine(cfg, params, datasets, "einsum")
