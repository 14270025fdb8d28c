"""Seeded weight draws shared by the served-weight builder and the reference.

Every weight tensor of a model is a pure function of (seed, model tag,
canonical leaf name, layer index), so the plain reference regenerates, one
layer at a time, exactly the values the server holds, without taking
anything the program made. Matmul weights are truncated normals at
1/sqrt(fan_in); RMS-norm weights are zero (applied as 1 + w).

The input embedding of an untied model is drawn at std 1.0. A tied table is
also the LM head, so it is drawn at 1/sqrt(d_model): the logits then have
std about 0.99 as an untied head's do, and speculative sampling at
temperature 1 accepts about half of the drafter's tokens. A table at std 1.0
would give logits of std sqrt(d_model) and near one-hot distributions.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: canonical per-layer leaves of the dense GQA / SwiGLU block
LAYER_LEAVES = ("norm1", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
                "norm2", "mlp.w_gate", "mlp.w_up", "mlp.w_down")


def base_key(seed: int):
    """Key for a seed of up to 64 bits (the low word seeds, the high word
    is folded in)."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _tag(s: str) -> np.uint32:
    return np.uint32(zlib.crc32(s.encode()))


def leaf_key(key, model: str, name: str, layer):
    """``layer`` may be traced (vmapped over the layer axis); -1 = none."""
    k = jax.random.fold_in(key, _tag(model))
    k = jax.random.fold_in(k, _tag(name))
    return jax.random.fold_in(k, layer + 1)


def leaf_shape(cfg: dict, name: str):
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv, ff, v = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"], cfg["vocab_size"]
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
            "norm1": (d,), "norm2": (d,),
            "attn.wq": (d, h * hd), "attn.wk": (d, kv * hd),
            "attn.wv": (d, kv * hd), "attn.wo": (h * hd, d),
            "mlp.w_gate": (d, ff), "mlp.w_up": (d, ff),
            "mlp.w_down": (ff, d)}[name]


def leaf_scale(cfg: dict, name: str) -> float:
    """Std of the draw; 0 for RMS-norm weights."""
    if name in ("norm1", "norm2", "final_norm"):
        return 0.0
    if name == "embed":
        return 1.0 / math.sqrt(cfg["d_model"]) if cfg["tie_embeddings"] else 1.0
    return 1.0 / math.sqrt(leaf_shape(cfg, name)[0])


def draw(key, cfg: dict, model: str, name: str, layer=-1):
    """float32 values of one leaf (one layer of it for per-layer leaves)."""
    shape = leaf_shape(cfg, name)
    scale = leaf_scale(cfg, name)
    if scale == 0.0:
        return jnp.zeros(shape, jnp.float32)
    k = leaf_key(key, model, name, layer)
    return scale * jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                               jnp.float32)
