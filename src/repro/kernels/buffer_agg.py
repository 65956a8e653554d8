"""Buffered weighted-sum Pallas kernel (FedPSA Eq. 20 apply step).

Aggregates the L_s buffered client updates into the global model in one
streaming pass: for each parameter block, read the (L, block) update slab
and the global block, emit global + sum_l w_l * update_l. One HBM read per
update element, one read+write of the global — no (L x d) temporary.

Block layout: updates are stored stacked (L, d) and viewed as (L, R, 128)
lane rows; the grid walks R in (block // 128)-row tiles, so every tile meets
the TPU's (8, 128) rule. The (L,) weights sit whole in SMEM and the sum over
L is an unrolled scalar-times-tile multiply-add on the VPU (Mosaic has no
1-D dot to lower an ``einsum("l,lb->b")`` to).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 8 * 128 * 8
LANES = 128


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> auto: compiled on TPU, interpreter everywhere else (the
    interpreter traces the kernel body to plain XLA ops, the CPU fallback)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _buffer_agg_kernel(w_ref, g_ref, u_ref, out_ref):
    acc = g_ref[...]                             # (rows, 128) f32
    for l in range(u_ref.shape[0]):              # L is small and static
        acc = acc + w_ref[l] * u_ref[l]
    out_ref[...] = acc


def buffer_agg_pallas(weights: jnp.ndarray, global_vec: jnp.ndarray,
                      updates: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """weights (L,), global_vec (d,), updates (L, d) -> (d,) f32.

    Layout-agnostic: under the d-sharded server this runs per-shard on the
    local ``d_local`` slice (the weighted sum is elementwise over d, so no
    cross-shard traffic). The block clamps to the vector width so a small
    shard is not padded out to the full 8k-lane default; it is a multiple of
    1024 (eight 128-lane rows, one compiled tile)."""
    interpret = resolve_interpret(interpret)
    L, d = updates.shape
    block = min(block, -(-d // 1024) * 1024)
    n = -(-d // block)
    dp = n * block
    rows, r = dp // LANES, block // LANES
    gv = jnp.pad(global_vec.astype(jnp.float32), [(0, dp - d)])
    up = jnp.pad(updates.astype(jnp.float32), [(0, 0), (0, dp - d)])

    out = pl.pallas_call(
        _buffer_agg_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((r, LANES), lambda i: (i, 0)),
            pl.BlockSpec((L, r, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((r, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        name="buffer_agg",
        interpret=interpret,
    )(weights.astype(jnp.float32), gv.reshape(rows, LANES),
      up.reshape(L, rows, LANES))
    return out.reshape(dp)[:d]
