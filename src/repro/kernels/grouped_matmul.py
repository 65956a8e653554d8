"""Grouped member-GEMM Pallas kernel.

One wave of B heterogeneous cohort members executes its dense layers as a
single grouped matmul over the stacked member axis: ``lhs (G, M, K) @ rhs
(G, K, N) -> (G, M, N)``, accumulated in f32 on the MXU. The per-group
``valid`` mask (held whole in SMEM, one scalar per group) turns ragged
bucket padding into exact no-op rows — padded member slots emit exact zeros
regardless of what garbage their padded params slab holds.

Grid: ``(G, nm, nn, nk)`` with the contraction innermost so each (g, i, j)
output tile is revisited across k-steps and accumulated in a VMEM f32
scratch tile; the finalize step applies the mask and casts to the promoted
input dtype. M pads to a multiple of 8 (f32 sublane), K/N to multiples of
128 (lane) — zero padding is exact under matmul.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.buffer_agg import resolve_interpret

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _caller_precision():
    """The caller's matmul precision, as far as Mosaic can express it: an
    f32 contraction under ``jax.default_matmul_precision("float32")`` (or
    ``"highest"``), else Mosaic's default MXU product — the same choice an
    XLA dot (the ``"vmap"`` member path) makes under the same setting."""
    p = jax.config.jax_default_matmul_precision
    return (jax.lax.Precision.HIGHEST
            if p is not None and p.lower() in ("float32", "highest") else None)


def _grouped_matmul_kernel(valid_ref, lhs_ref, rhs_ref, out_ref, acc_ref,
                           *, nk: int, precision):
    g, kk = pl.program_id(0), pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = lhs_ref[0].astype(jnp.float32)            # (bm, bk)
    b = rhs_ref[0].astype(jnp.float32)            # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=precision)

    @pl.when(kk == nk - 1)
    def _done():
        out_ref[0] = (acc_ref[...] * valid_ref[g]).astype(out_ref.dtype)


def grouped_matmul_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray,
                          valid: Optional[jnp.ndarray] = None, *,
                          block_m: int = DEFAULT_BLOCK_M,
                          block_n: int = DEFAULT_BLOCK_N,
                          block_k: int = DEFAULT_BLOCK_K,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """``lhs (G, M, K) @ rhs (G, K, N) -> (G, M, N)``, f32 accumulation.

    ``valid`` is an optional (G,) mask (bool or float); groups with
    ``valid == 0`` produce exact-zero output tiles. Blocks clamp to the
    (padded) problem so tiny smoke shapes are not tiled out to 128^3.
    """
    interpret = resolve_interpret(interpret)
    G, M, K = lhs.shape
    G2, K2, N = rhs.shape
    assert (G, K) == (G2, K2), (lhs.shape, rhs.shape)
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)

    bm = min(block_m, _round_up(M, 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk

    lp = jnp.pad(lhs, [(0, 0), (0, Mp - M), (0, Kp - K)])
    rp = jnp.pad(rhs, [(0, 0), (0, Kp - K), (0, Np - N)])
    if valid is None:
        v = jnp.ones((G,), jnp.float32)
    else:
        v = valid.astype(jnp.float32).reshape(G)

    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, nk=nk,
                          precision=_caller_precision()),
        grid=(G, nm, nn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bk), lambda g, i, j, kk: (g, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, kk: (g, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, kk: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="grouped_matmul",
        interpret=interpret,
    )(v, lp, rp)
    return out[:, :M, :N]
