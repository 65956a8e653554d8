"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached, so
these run on a CPU-only host: each kernel is lowered with ``interpret=False``
at the widths the paper's CIFAR-10 CNN runs them (d = 1,756,426 flat
parameters, its 1,572,864-element ``fc0.w`` leaf, the fc0 member GEMM at
batch 64) and at a tiny edge size, and must come out as a Mosaic kernel
(``tpu_custom_call``). What Mosaic refuses — a block off the (8, 128)
tiling, a dot form it cannot parse — fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.buffer_agg import buffer_agg_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.sens_sketch import sens_sketch_pallas

D_CNN = 1_756_426       # paper-cifar10-cnn flat parameter count
D_FC0 = 1_572_864       # its fc0.w leaf (4096 x 384)


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2 host; the persistent
    compilation cache is off meanwhile (entries compiled for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo.devices
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """A single-device sharding on the described host's first chip."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


def _compile_to_mosaic(fn, name, sharding, *shapes):
    """Compile ``fn``; it must hold a Mosaic kernel, and that kernel must
    carry its stable ``name`` (the instruction a profiler trace shows)."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    assert all(line.split("=")[0].split()[-1].startswith(f"%{name}")
               for line in kernels)


@pytest.mark.parametrize("L,d", [(5, D_CNN), (5, 10)])
def test_buffer_agg_compiles(one_chip, L, d):
    _compile_to_mosaic(functools.partial(buffer_agg_pallas, interpret=False),
                       "buffer_agg", one_chip, (L,), (d,), (L, d))


@pytest.mark.parametrize("d", [D_FC0, 10])
def test_sens_sketch_compiles(one_chip, d):
    _compile_to_mosaic(functools.partial(sens_sketch_pallas, k=16, seed=3,
                                         interpret=False),
                       "sens_sketch", one_chip, (d,), (d,), (d,))


def test_grouped_matmul_compiles(one_chip):
    G, M, K, N = 4, 64, 4096, 384       # fc0 at batch 64, a 4-member bucket
    _compile_to_mosaic(functools.partial(grouped_matmul_pallas,
                                         interpret=False),
                       "grouped_matmul", one_chip, (G, M, K), (G, K, N),
                       (G,))


@pytest.mark.parametrize("axis,rows", [
    pytest.param("d", 4, id="d"), pytest.param(None, 4, id="None"),
    pytest.param(None, 1, id="lone")])
def test_wave_sketch_compiles_on_mesh(v5e_2x2, monkeypatch, axis, rows):
    """The cohort engine's wave sketch on a four-chip mesh: a Mosaic kernel
    is never partitioned automatically, so the batched sketch must hold it
    inside ``shard_map`` — over the wave's sharded cohort axis, or
    replicated when the bucket does not divide the mesh (a lone member's
    1-row wave among them)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.common import tree as tu
    from repro.configs import get_config
    from repro.core import PSAConfig
    from repro.federated import simulator
    from repro.models import model as model_lib

    # the library picks the compiled kernel only when the backend is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(v5e_2x2), ("d",))
    cfg = get_config("paper-synthetic-mlp")
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    spec = tu.FlatSpec(params)
    calib = {"x": np.zeros((8, cfg.input_hw[0]), np.float32),
             "y": np.zeros((8,), np.int32)}
    fn = simulator._build_sketch_fn_flat(cfg, calib, PSAConfig(), spec,
                                         mesh, "d")
    w = jax.ShapeDtypeStruct((rows, spec.size), jnp.float32,
                             sharding=NamedSharding(mesh, P(axis)))
    assert "tpu_custom_call" in jax.jit(fn).lower(w).compile().as_text()
