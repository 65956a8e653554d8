"""FedPSA core math vs the paper's equations (Eq. 3-20).

Property-based (hypothesis) variants of these invariants live in
``tests/test_property.py`` behind ``pytest.importorskip``; everything here
runs on a bare pytest install.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PSAConfig, aggregate_buffer, buffer_full, cosine,
                        dense_projection, fisher_diagonal, init_state,
                        init_thermometer, is_full, psa_weights, push,
                        sensitivity, sensitivity_from_parts, server_aggregate,
                        server_receive, server_step, sketch_tree,
                        staleness_polynomial, temperature, uniform_weights)
from repro.core import psa as psa_lib
from repro.common import tree as tu


def _quad_loss(params, batch):
    """loss = 0.5 * sum((x @ w - y)^2) / B — analytic grads & Fisher."""
    pred = batch["x"] @ params["w"]
    return 0.5 * jnp.mean(jnp.sum(jnp.square(pred - batch["y"]), -1))


def test_sensitivity_matches_manual_eq8():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 3))
    params = {"w": w}
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 4))
    y = jax.random.normal(jax.random.fold_in(key, 2), (8, 3))
    batch = {"x": x, "y": y}
    s = sensitivity(_quad_loss, params, batch, num_micro=4)["w"]

    g = jax.grad(_quad_loss)(params, batch)["w"]
    # empirical Fisher: mean over the 4 microbatches of squared microbatch grads
    fs = []
    for i in range(4):
        mb = {"x": x[2 * i:2 * i + 2], "y": y[2 * i:2 * i + 2]}
        fs.append(jnp.square(jax.grad(_quad_loss)(params, mb)["w"]))
    F = sum(fs) / 4
    want = jnp.abs(g * w - 0.5 * F * jnp.square(w))
    np.testing.assert_allclose(np.asarray(s), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_sensitivity_second_order_approximates_zeroing():
    """Eq. 3 ground truth: |F(theta) - F(theta - theta_i e_i)| vs Eq. 8,
    on a quadratic loss where the 2nd-order Taylor expansion is EXACT in the
    Hessian — the empirical-Fisher approximation is the only error source.
    Evaluated near the optimum (the regime the paper's sensitivity targets)
    with the program's 4 Fisher microbatches of m = 16 samples. A microbatch
    gradient's variance is sigma^2 H / m, so labels carry noise of sigma =
    sqrt(m) = 4 for the empirical Fisher to estimate the Hessian, which
    Eq. 8 assumes; without noise it is orders smaller and Eq. 8 reduces to
    |g theta|. 24 parameters per draw (a 6-point rank correlation is too
    coarse), averaged over 16 draws from numpy's seeded stream, so the
    verdict does not hang on JAX's PRNG implementation."""
    def rank(a):
        order = np.argsort(a.ravel())
        r = np.empty_like(order)
        r[order] = np.arange(len(order))
        return r

    n, micro, shape = 64, 4, (6, 4)
    corrs = []
    for seed in range(16):
        rng = np.random.RandomState(seed)
        w_true = rng.randn(*shape).astype(np.float32)
        w = w_true + 0.3 * rng.randn(*shape).astype(np.float32)
        x = rng.randn(n, shape[0]).astype(np.float32)
        noise = np.sqrt(n // micro) * rng.randn(n, shape[1])
        y = x @ w_true + noise.astype(np.float32)
        params = {"w": jnp.asarray(w)}
        batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
        s = np.asarray(sensitivity(_quad_loss, params, batch,
                                   num_micro=micro)["w"])

        base = float(_quad_loss(params, batch))
        true = np.zeros_like(s)
        for i, j in np.ndindex(*shape):
            wz = w.copy()
            wz[i, j] = 0.0
            true[i, j] = abs(
                base - float(_quad_loss({"w": jnp.asarray(wz)}, batch)))
        corrs.append(np.corrcoef(rank(s), rank(true))[0, 1])
    # the approximation must order parameters like the truth, on average
    assert np.mean(corrs) > 0.7, corrs
    assert min(corrs) > 0.3, corrs


def test_sketch_equals_dense_projection():
    key = jax.random.PRNGKey(2)
    tree = {"a": jax.random.normal(key, (9, 5)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (7,))}
    for k in (4, 16, 64):
        y = sketch_tree(tree, seed=11, k=k)
        R = dense_projection(11, [l.shape for l in jax.tree_util.tree_leaves(tree)], k)
        flat = np.concatenate([np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(tree)])
        np.testing.assert_allclose(np.asarray(y), R @ flat, rtol=1e-4, atol=1e-4)


def test_sketch_vectorized_bit_identical_to_unrolled():
    """The vectorized default and the legacy per-row loop hash the same
    indices with the same uint32 math — bit-identical sums, not just close."""
    key = jax.random.PRNGKey(4)
    tree = {"a": jax.random.normal(key, (13, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (31,)),
            "c": jnp.float32(0.5)}  # scalar leaf
    for k in (4, 16):
        vec = sketch_tree(tree, seed=3, k=k)
        loop = sketch_tree(tree, seed=3, k=k, unroll=True)
        np.testing.assert_array_equal(np.asarray(vec), np.asarray(loop))


def test_sketch_compile_time_budget():
    """Compile-time regression guard: the vectorized sketch of the fed-lm
    smoke parameter tree must trace+compile in seconds. The unrolled legacy
    form took ~85s here (k x n_leaves distinct hash/reduce chains) — a
    regression back to per-row programs blows this budget immediately."""
    import time

    from repro.configs import get_config
    from repro.models import model as model_lib

    cfg = get_config("fed-lm-smoke")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    f = jax.jit(lambda p: sketch_tree(p, 0, 16))
    t0 = time.time()
    f.lower(params).compile()
    elapsed = time.time() - t0
    assert elapsed < 30.0, f"fed-lm sketch compile took {elapsed:.1f}s"


def test_cosine_bounds():
    for seed in range(20):
        rng = np.random.RandomState(seed)
        a = jnp.asarray(rng.randn(16).astype(np.float32))
        b = jnp.asarray(rng.randn(16).astype(np.float32))
        c = float(cosine(a, b))
        assert -1.0001 <= c <= 1.0001
        assert abs(float(cosine(a, a)) - 1.0) < 1e-5


def test_jl_cosine_preservation():
    """JL (Eq. 14-15): sketch cosine approximates full cosine."""
    rng = np.random.RandomState(0)
    d, k = 4096, 128
    errs = []
    for t in range(10):
        a = rng.randn(d).astype(np.float32)
        b = (0.6 * a + 0.4 * rng.randn(d)).astype(np.float32)
        sa = sketch_tree({"x": jnp.asarray(a)}, seed=t, k=k)
        sb = sketch_tree({"x": jnp.asarray(b)}, seed=t, k=k)
        full = float(np.dot(a, b) / np.linalg.norm(a) / np.linalg.norm(b))
        errs.append(abs(full - float(cosine(sa, sb))))
    assert np.mean(errs) < 0.08, errs


def test_thermometer_eq16_18():
    st_ = init_thermometer(4)
    assert not bool(is_full(st_))
    for m in (4.0, 4.0, 4.0, 4.0):
        st_ = push(st_, m)
    assert bool(is_full(st_))
    assert float(st_.m0) == 4.0
    # Temp = (M_cur/M_0)*gamma + delta
    assert abs(float(temperature(st_, 5.0, 0.5)) - 5.5) < 1e-6
    for m in (1.0, 1.0, 1.0, 1.0):  # ring overwrites, M_cur = 1
        st_ = push(st_, m)
    assert abs(float(temperature(st_, 5.0, 0.5)) - (0.25 * 5 + 0.5)) < 1e-6


def test_psa_weights_simplex():
    for seed in range(20):
        rng = np.random.RandomState(seed)
        kappas = rng.uniform(-1, 1, size=rng.randint(2, 9)).astype(np.float32)
        temp = float(rng.uniform(0.125, 20.0))
        w = np.asarray(psa_weights(jnp.asarray(kappas), jnp.float32(temp)))
        assert abs(w.sum() - 1.0) < 1e-4
        assert (w >= 0).all()
        # monotone: higher kappa never gets lower weight
        order = np.argsort(kappas)
        assert (np.diff(w[order]) >= -1e-6).all()


def test_temperature_sharpens_weights():
    k = jnp.asarray([0.9, 0.1, -0.5])
    w_hot = np.asarray(psa_weights(k, jnp.float32(10.0)))
    w_cold = np.asarray(psa_weights(k, jnp.float32(0.1)))
    assert w_cold[0] > w_hot[0]          # cold focuses on the best update
    assert w_cold[0] > 0.99
    assert np.std(w_hot) < np.std(w_cold)


def test_algorithm1_uniform_until_queue_full():
    cfg = PSAConfig(buffer_size=2, queue_len=6)
    d = 3
    state = init_state(cfg, d, jnp.ones(cfg.sketch_k))
    params = jnp.zeros((d,))
    infos = []
    for i in range(6):  # 3 aggregations x buffer 2 = 6 receives = queue fills
        upd = jnp.full((d,), 0.1 * (i + 1))
        sk = jnp.ones(cfg.sketch_k) * (1.0 if i % 2 == 0 else -1.0)
        state = server_receive(state, upd, sk)
        if bool(buffer_full(state)):
            state, params, info = server_aggregate(state, params, cfg)
            infos.append(info)
    # first aggregations: queue not yet full -> uniform
    np.testing.assert_allclose(np.asarray(infos[0].weights), [0.5, 0.5], atol=1e-6)
    assert not bool(infos[0].temp_valid)
    # last aggregation: queue full -> temperature softmax, kappa +1 vs -1
    assert bool(infos[-1].temp_valid) and float(infos[-1].temp) > 0
    w = np.asarray(infos[-1].weights)
    assert w[0] > w[1]  # kappa=+1 entry outweighs kappa=-1


def test_psa_stacked_ring_buffer_semantics():
    """The (L_s, d) stacked buffer behaves as a ring: slot j of push n lands
    at n % L_s, the fill count tracks receives and resets on aggregation."""
    cfg = PSAConfig(buffer_size=3, queue_len=8)
    d = 4
    state = init_state(cfg, d, jnp.ones(cfg.sketch_k))
    updates = [jnp.full((d,), float(i + 1)) for i in range(5)]
    for i, u in enumerate(updates[:2]):
        state = server_receive(state, u, jnp.ones(cfg.sketch_k))
        assert int(state.count) == i + 1
        assert not bool(buffer_full(state))
        np.testing.assert_allclose(np.asarray(state.buffer[i]), np.asarray(u))
    state = server_receive(state, updates[2], jnp.ones(cfg.sketch_k))
    assert bool(buffer_full(state))
    state, _, _ = server_aggregate(state, jnp.zeros((d,)), cfg)
    assert int(state.count) == 0
    # next cycle overwrites slots starting at 0 (implicit clear)
    state = server_receive(state, updates[3], jnp.ones(cfg.sketch_k))
    np.testing.assert_allclose(np.asarray(state.buffer[0]),
                               np.asarray(updates[3]))
    assert int(state.thermo.count) == 4  # thermometer tracks ALL receives


def test_fused_server_step_matches_two_phase():
    """server_step (lax.cond fused) == server_receive + server_aggregate."""
    cfg = PSAConfig(buffer_size=2, queue_len=4)
    d = 6
    rng = np.random.RandomState(3)
    sketches = [jnp.asarray(rng.randn(cfg.sketch_k), jnp.float32)
                for _ in range(8)]
    updates = [jnp.asarray(rng.randn(d) * 0.1, jnp.float32) for _ in range(8)]

    gs = jnp.asarray(rng.randn(cfg.sketch_k), jnp.float32)
    s_a = init_state(cfg, d, gs)
    s_b = init_state(cfg, d, gs)
    g_a = jnp.zeros((d,))
    g_b = jnp.zeros((d,))
    fused = jax.jit(lambda st, g, u, sk: server_step(st, g, u, sk, cfg))
    for u, sk in zip(updates, sketches):
        s_a, g_a, info = fused(s_a, g_a, u, sk)
        s_b = server_receive(s_b, u, sk)
        if bool(buffer_full(s_b)):
            s_b, g_b, _ = server_aggregate(s_b, g_b, cfg)
            assert bool(info.updated)
        else:
            assert not bool(info.updated)
        np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_b),
                                   rtol=1e-6, atol=1e-6)
    assert int(s_a.count) == int(s_b.count)


def test_staleness_polynomial_decreasing():
    taus = jnp.arange(0, 20)
    w = np.asarray(staleness_polynomial(taus))
    assert (np.diff(w) < 0).all()
    assert abs(w[0] - 0.6) < 1e-6
