"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.core.sensitivity import sensitivity_from_parts
from repro.kernels import ops, ref
from repro.kernels.buffer_agg import buffer_agg_pallas
from repro.kernels.sens_sketch import sens_sketch_pallas


@pytest.mark.parametrize("d", [1, 7, 512, 1024, 4097, 20000])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sens_sketch_shapes_dtypes(d, dtype):
    key = jax.random.PRNGKey(d)
    dt = jnp.dtype(dtype)
    theta = jax.random.normal(key, (d,), dt)
    g = jax.random.normal(jax.random.fold_in(key, 1), (d,), dt)
    f = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (d,), dt))
    out = sens_sketch_pallas(theta, g, f, k=16, seed=3, block=1024, interpret=True)
    want = ref.sens_sketch_ref(theta.astype(jnp.float32), g.astype(jnp.float32),
                               f.astype(jnp.float32), k=16, seed=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("k", [1, 4, 16, 32])
def test_sens_sketch_k_sweep(k):
    key = jax.random.PRNGKey(k)
    d = 3000
    theta, g = (jax.random.normal(jax.random.fold_in(key, i), (d,)) for i in range(2))
    f = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (d,)))
    out = sens_sketch_pallas(theta, g, f, k=k, seed=0, block=512, interpret=True)
    want = ref.sens_sketch_ref(theta, g, f, k=k, seed=0)
    assert out.shape == (k,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_sens_sketch_block_invariance():
    key = jax.random.PRNGKey(9)
    d = 10240
    theta, g = (jax.random.normal(jax.random.fold_in(key, i), (d,)) for i in range(2))
    f = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (d,)))
    outs = [sens_sketch_pallas(theta, g, f, k=8, seed=1, block=b, interpret=True)
            for b in (256, 1024, 2048)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-4, atol=1e-4)


def test_sens_sketch_under_vmap_matches_rows():
    """The batched client-sketch path vmaps the kernel over a wave's members
    (the batch axis becomes a leading grid axis): each row's sketch must
    equal the unbatched kernel's on that row."""
    key = jax.random.PRNGKey(4)
    B, d = 3, 5000
    theta, g = (jax.random.normal(jax.random.fold_in(key, i), (B, d))
                for i in range(2))
    f = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (B, d)))
    got = jax.vmap(lambda a, b, c: sens_sketch_pallas(
        a, b, c, k=16, seed=2, block=2048, interpret=True))(theta, g, f)
    for i in range(B):
        want = ref.sens_sketch_ref(theta[i], g[i], f[i], k=16, seed=2)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_sens_sketch_shards_compose_via_index_offset():
    """d-sharded contract: the sum of per-shard sketches computed with
    ``index_offset`` set to each shard's global start equals the full-vector
    sketch — the projection sign of element i depends only on its global
    index, so per-shard partials psum to the exact single-device result."""
    key = jax.random.PRNGKey(3)
    d = 4096 + 640   # not a multiple of typical shard counts' blocks
    theta, g = (jax.random.normal(jax.random.fold_in(key, i), (d,))
                for i in range(2))
    f = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (d,)))
    full = sens_sketch_pallas(theta, g, f, k=8, seed=11, interpret=True)
    for nshards in (2, 4):
        bounds = np.linspace(0, d, nshards + 1).astype(int)
        parts = [
            sens_sketch_pallas(theta[lo:hi], g[lo:hi], f[lo:hi], k=8,
                               seed=11, index_offset=int(lo), interpret=True)
            for lo, hi in zip(bounds[:-1], bounds[1:])
        ]
        np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full),
                                   rtol=1e-4, atol=1e-4)


def test_fused_tree_sketch_matches_core_pipeline():
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (40, 30)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (55,))}}
    g = jax.tree_util.tree_map(lambda x: 0.3 * x + 0.01, tree)
    f = jax.tree_util.tree_map(jnp.abs, tree)
    want = sk.sketch_tree(sensitivity_from_parts(tree, g, f), seed=5, k=16)
    got = ops.sketch_tree_fused(tree, g, f, seed=5, k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,d", [(1, 64), (5, 3000), (8, 8193), (20, 100)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_buffer_agg_shapes_dtypes(L, d, dtype):
    key = jax.random.PRNGKey(L * d)
    dt = jnp.dtype(dtype)
    w = jax.nn.softmax(jax.random.normal(key, (L,)))
    gv = jax.random.normal(jax.random.fold_in(key, 1), (d,), dt)
    ups = jax.random.normal(jax.random.fold_in(key, 2), (L, d), dt)
    out = buffer_agg_pallas(w, gv, ups, block=1024, interpret=True)
    want = ref.buffer_agg_ref(w, gv, ups)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=5e-3, atol=5e-3)


def test_buffer_agg_matches_tree_weighted_sum_semantics():
    """The kernel is exactly Eq. 20 over a flattened pytree."""
    from repro.common import tree as tu
    key = jax.random.PRNGKey(7)
    trees = [{"w": jax.random.normal(jax.random.fold_in(key, i), (17, 3))}
             for i in range(4)]
    weights = jax.nn.softmax(jax.random.normal(key, (4,)))
    g = {"w": jax.random.normal(jax.random.fold_in(key, 99), (17, 3))}
    want = tu.tree_add(g, tu.tree_weighted_sum(trees, weights))
    gv, unflatten = tu.flatten_to_vector(g)
    ups = jnp.stack([tu.flatten_to_vector(t)[0] for t in trees])
    got = unflatten(ops.buffer_agg(weights, gv, ups))
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                               rtol=1e-5, atol=1e-5)
