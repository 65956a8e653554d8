"""Fused sensitivity + sketch Pallas TPU kernel.

The hot loop of FedPSA's client upload path: for every parameter block,
compute the Eq. 8 sensitivity s = |g*theta - 0.5*F*theta^2| and immediately
contract it against the on-the-fly Rademacher projection rows, accumulating
the k-vector sketch in VMEM. HBM traffic is exactly one streaming read of
(theta, g, F) per block — the d-sized sensitivity vector is NEVER written to
HBM, and the (k x d) projection matrix is never materialized (it is hashed
from the block's linear indices inside the kernel).

TPU adaptation notes (DESIGN.md §3): the paper's GPU implementation builds s
in device memory and multiplies by a broadcast dense R. On TPU we fuse both
into one VMEM-resident pass; the per-row sign generation is VPU integer work
that overlaps the float multiply-accumulate. The flat vectors are viewed as
(R, 128) lane rows and a block is (block // 128, 128), so a compiled block
meets the (8, 128) tiling rule — also under ``vmap``, which the batched
client-sketch path applies (the batch axis becomes a leading grid axis).

Grid: one program per parameter block; the (1, k) output block is revisited
by every program (index_map -> 0) and accumulated sequentially, the standard
Pallas reduction pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.buffer_agg import LANES, resolve_interpret

DEFAULT_BLOCK = 8 * 128 * 8  # 8192 f32 lanes per program


def _pcg(x):
    x = x.astype(jnp.uint32)
    state = x * jnp.uint32(747796405) + jnp.uint32(2891336453)
    word = ((state >> ((state >> jnp.uint32(28)) + jnp.uint32(4))) ^ state)
    word = word * jnp.uint32(277803737)
    return (word >> jnp.uint32(22)) ^ word


def _sens_sketch_kernel(theta_ref, g_ref, f_ref, out_ref, *, k: int,
                        seed: int, rows: int, index_offset: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    theta = theta_ref[...].astype(jnp.float32)   # (rows, 128)
    g = g_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    # Eq. 8 sensitivity, fused
    s = jnp.abs(g * theta - 0.5 * f * jnp.square(theta))

    shape = (rows, LANES)
    lin = (jnp.uint32(index_offset)
           + pid.astype(jnp.uint32) * jnp.uint32(rows * LANES)
           + jax.lax.broadcasted_iota(jnp.uint32, shape, 0) * jnp.uint32(LANES)
           + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    seed_u = jnp.uint32(seed)
    partial = []
    for r in range(k):  # unrolled: k is small (paper: 16)
        h = _pcg(seed_u ^ _pcg(lin * jnp.uint32(k) + jnp.uint32(r)))
        sign = jnp.where((h >> jnp.uint32(31)) == 0, 1.0, -1.0).astype(jnp.float32)
        partial.append(jnp.sum(s * sign))
    out_ref[...] += jnp.stack(partial).reshape(1, k)


def sens_sketch_pallas(theta: jnp.ndarray, g: jnp.ndarray, f: jnp.ndarray,
                       *, k: int = 16, seed: int = 0,
                       block: int = DEFAULT_BLOCK,
                       index_offset: int = 0,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused sensitivity+sketch of FLAT vectors theta/g/f -> (k,) f32.

    Inputs are zero-padded to a block multiple (padded entries have s = 0, so
    they contribute nothing regardless of their projection sign). The result
    includes the 1/sqrt(k) JL scale, matching ``repro.core.sketch``.
    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere.
    ``block`` rounds up to whole 128-lane rows; compiled on TPU it must be a
    multiple of 1024 (8 rows), which the default and the small-vector clamp
    are.

    ``index_offset`` shifts the Rademacher hash to GLOBAL parameter indices:
    a caller holding shard ``theta[o : o + d_local]`` of a d-sharded flat
    vector passes ``index_offset=o``, and the psum of the per-shard partial
    sketches equals the single-device sketch of the full vector exactly
    (the projection sign of element i depends only on its global index).
    """
    interpret = resolve_interpret(interpret)
    (d,) = theta.shape
    block = min(block, -(-d // 1024) * 1024)  # don't pad small shards to 8k
    rows = -(-block // LANES)
    block = rows * LANES
    n = -(-d // block)
    dp = n * block
    pad = [(0, dp - d)]
    theta, g, f = (jnp.pad(x.astype(jnp.float32), pad).reshape(n * rows, LANES)
                   for x in (theta, g, f))

    out = pl.pallas_call(
        functools.partial(_sens_sketch_kernel, k=k, seed=seed, rows=rows,
                          index_offset=index_offset),
        grid=(n,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        name="sens_sketch",
        interpret=interpret,
    )(theta, g, f)
    return out[0] / jnp.sqrt(jnp.float32(k))
