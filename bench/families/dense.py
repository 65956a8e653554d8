"""A dense decoder-only LM: the program's ``dense`` family with attention
mixers and SwiGLU feed-forward layers (``configs/fed_lm.py``'s
``fed-lm-smoke``).

The plain reference is written from the equations of the program's
``models/model.py`` and ``models/layers.py``, not imported:

- tokens are looked up in the embedding table (``tok``, rows padded to a
  multiple of ``pad_vocab_to``);
- each of ``num_layers`` layers is pre-norm: ``x += attn(rms(x))``, then
  ``x += ffn(rms(x))``, with RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``;
  attention projects to ``num_heads`` query heads and ``num_kv_heads`` key
  and value heads of ``head_dim`` (query head ``h`` reads key head
  ``h // (num_heads / num_kv_heads)``), rotates queries and keys by RoPE
  (frequencies ``theta^(-2i/head_dim)`` on interleaved pairs), and takes a
  causal softmax of ``q.k / sqrt(head_dim)``; the feed-forward layer is
  ``(silu(x W_gate) * x W_in) W_out``;
- a final RMSNorm, then logits against the untied ``unembed`` table, whose
  pad columns are left out;
- the loss is next-token cross-entropy: position t predicts token t + 1,
  averaged over the targets of the rows with ``weight`` 1 (the program
  turns a masked row's labels into -1, no target).

The program stacks each leaf of the layers along a leading layer axis and
flattens the tree in its sorted key order; ``leaf_names`` gives the same
order. Every leaf is trained.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as tr
from bench.token_world import BATCH_KEYS, make_world  # noqa: F401
from bench.traffic import program_inputs  # noqa: F401

FIELDS = ("family", "num_layers", "d_model", "num_heads", "num_kv_heads",
          "head_dim", "d_ff", "vocab_size", "pad_vocab_to", "block_pattern",
          "ffn_pattern", "ffn_act", "tie_embeddings", "causal", "rope_theta",
          "norm_eps", "sliding_window", "frontend", "param_dtype", "dtype")


def _supported(config: dict) -> None:
    want = {"block_pattern": ["attn"], "ffn_pattern": ["dense"],
            "ffn_act": "swiglu", "tie_embeddings": False, "causal": True,
            "sliding_window": None, "frontend": None}
    for key, value in want.items():
        if config[key] != value:
            raise ValueError(f"{config['name']}: the dense reference has "
                             f"{key} {value!r}, the file states "
                             f"{config[key]!r}")


def _vocab_padded(config: dict) -> int:
    m = int(config["pad_vocab_to"])
    return -(-int(config["vocab_size"]) // m) * m


def _tree(config: dict) -> dict:
    """The program's parameter tree, as shapes."""
    _supported(config)
    L, D = int(config["num_layers"]), int(config["d_model"])
    H, Hkv = int(config["num_heads"]), int(config["num_kv_heads"])
    hd, F = int(config["head_dim"]), int(config["d_ff"])
    Vp = _vocab_padded(config)
    block = {"norm1": {"scale": (L, D)},
             "mixer": {"wq": (L, D, H, hd), "wk": (L, D, Hkv, hd),
                       "wv": (L, D, Hkv, hd), "wo": (L, H, hd, D)},
             "norm2": {"scale": (L, D)},
             "ffn": {"w_in": (L, D, F), "w_gate": (L, D, F),
                     "w_out": (L, F, D)}}
    return {"blocks": {"p0": block},
            "embed": {"tok": (Vp, D), "unembed": (D, Vp)},
            "final_norm": {"scale": (D,)}}


def _paths(tree: dict, prefix=()) -> List[tuple]:
    out = []
    for key in sorted(tree):
        if isinstance(tree[key], dict):
            out += _paths(tree[key], prefix + (key,))
        else:
            out.append(prefix + (key,))
    return out


def leaf_names(config: dict) -> List[tuple]:
    return _paths(_tree(config))


def leaf_shapes(config: dict) -> Dict[tuple, tuple]:
    tree = _tree(config)
    out = {}
    for path in _paths(tree):
        node = tree
        for key in path:
            node = node[key]
        out[path] = node
    return out


def params(config: dict) -> int:
    return int(sum(np.prod(s) for s in leaf_shapes(config).values()))


def init_weights(config: dict, seed: int) -> dict:
    """The run's initial weights, made on the device in one jitted call, in
    float32: norm scales of one; truncated-normal weights at 1/sqrt(fan-in)
    (the output projection over its heads and head size, the embedding
    table at 1); the vocabulary's pad rows and columns zero."""
    shapes = leaf_shapes(config)
    V = int(config["vocab_size"])

    def leaf(key, path, shp):
        name = path[-1]
        if name == "scale":
            return jnp.ones(shp, jnp.float32)
        w = jax.random.truncated_normal(key, -2.0, 2.0, shp, jnp.float32)
        if name == "tok":
            return jnp.where(jnp.arange(shp[0])[:, None] < V, w, 0.0)
        if name == "unembed":
            return jnp.where(jnp.arange(shp[1])[None, :] < V,
                             w / np.sqrt(shp[0]), 0.0)
        fan_in = shp[1] * shp[2] if name == "wo" else shp[1]
        return w / np.sqrt(fan_in)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        out: dict = {}
        for kk, (path, shp) in zip(keys, shapes.items()):
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = leaf(kk, path, shp)
        return out

    return make(jax.random.PRNGKey(tr.sub_seeds(seed)["model"]))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _rms(scale, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x (B, S, heads, hd): interleaved pairs rotated by position."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(p, x, config, dtype, prec):
    B, S, _ = x.shape
    H, Hkv = int(config["num_heads"]), int(config["num_kv_heads"])
    hd = int(config["head_dim"])
    theta = float(config["rope_theta"])
    q = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype),
                         precision=prec), theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype),
                         precision=prec), theta)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype), precision=prec)
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqhgk,bthk->bhgqt", q, k, precision=prec) / np.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqt,bthk->bqhgk", a, v, precision=prec)
    return jnp.einsum("bshk,hkd->bsd", o.reshape(B, S, H, hd),
                      p["wo"].astype(dtype), precision=prec)


def _ffn(p, x, dtype, prec):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dtype),
                   precision=prec)
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(dtype), precision=prec)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h,
                      p["w_out"].astype(dtype), precision=prec)


def forward(params, tokens, config: dict, dtype=jnp.float32):
    """(B, S) token ids -> (B, S, vocab_size) logits."""
    prec = _precision(dtype)
    eps = float(config["norm_eps"])
    x = params["embed"]["tok"].astype(dtype)[tokens]
    blocks = params["blocks"]["p0"]
    for layer in range(int(config["num_layers"])):
        p = jax.tree_util.tree_map(lambda a: a[layer].astype(dtype), blocks)
        x = x + _attention(p["mixer"], _rms(p["norm1"]["scale"], x, eps),
                           config, dtype, prec)
        x = x + _ffn(p["ffn"], _rms(p["norm2"]["scale"], x, eps), dtype,
                     prec)
    x = _rms(params["final_norm"]["scale"].astype(dtype), x, eps)
    V = int(config["vocab_size"])
    return jnp.einsum("bsd,dv->bsv", x,
                      params["embed"]["unembed"][:, :V].astype(dtype),
                      precision=prec)


def loss(params, batch, weight, config: dict, dtype=jnp.float32):
    """Next-token cross-entropy over the targets of the rows with
    ``weight`` 1 (labels below 0 are no target)."""
    logits = forward(params, batch["tokens"], config, dtype)[:, :-1]
    labels = batch["labels"][:, 1:]
    mask = ((labels >= 0) & (weight[:, None] > 0)).astype(dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1)


# ---------------------------------------------------------------------------
# Operations
# ---------------------------------------------------------------------------

def forward_flops(config: dict, traffic: dict) -> float:
    """One sequence of the mix's ``seq_len`` tokens: two operations per
    multiply-add of the projections, of the causal attention's scores and
    weighted values (the pairs ``t' <= t``), of the feed-forward layers and
    of the logits of the positions that have a target (all but the last).
    Norms, RoPE, the softmax and the lookup are not counted."""
    S = int(traffic["seq_len"])
    D, F = int(config["d_model"]), int(config["d_ff"])
    H, Hkv = int(config["num_heads"]), int(config["num_kv_heads"])
    hd, V = int(config["head_dim"]), int(config["vocab_size"])
    proj = 2 * S * D * hd * (2 * H + 2 * Hkv)
    attn = 2 * 2 * H * hd * S * (S + 1) // 2
    ffn = 2 * S * 3 * D * F
    return float(int(config["num_layers"]) * (proj + attn + ffn)
                 + 2 * (S - 1) * D * V)


def train_flops(config: dict, traffic: dict, counters: dict) -> float:
    """Three forward passes' worth for every real sequence of every real
    step in the window."""
    return 3.0 * forward_flops(config, traffic) * counters["samples"]
