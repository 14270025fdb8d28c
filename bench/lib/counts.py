"""Operations and bytes a decode round needs, read from the served arrays.

Parameter counts, dtypes and head sizes come from the served weight and
pool arrays themselves, so a change of their dtype (int8 weights, an int8
pool) or of the head's tying changes the count with it.

Round (chain speculation, gamma drafts), over the active rows only:
  target  one forward over gamma + 1 positions per row
  drafter gamma + 1 single-position passes per row
  attention at each row's committed length L: 4 * H * hd * L operations
          per query position per layer (q.k and p.v)
Bytes: the target's matmul and head weights once, the drafter's once per
pass, and each row's live K/V (L tokens) once per pass of each model.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.tree_util import DictKey


def _names(path):
    return [p.key for p in path if isinstance(p, DictKey)]


@dataclass(frozen=True)
class ModelCounts:
    matmul_params: int       # weights a token passes through, head included
    matmul_bytes: int
    layers: int
    heads: int
    head_dim: int
    kv_bytes_per_token: int  # K and V of every layer, from the pool


def model_counts(params, pool) -> ModelCounts:
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    tied = not any(_names(p)[:1] == ["lm_head"] for p, _ in leaves)
    n = nbytes = 0
    layers = heads = 0
    for path, x in leaves:
        names = _names(path)
        if "norm" in names[-1] or (names[:1] == ["embed"] and not tied):
            continue       # norms; an untied input table is only gathered
        n += int(np.prod(x.shape))
        nbytes += int(np.prod(x.shape)) * x.dtype.itemsize
        if names[-1] == "wq":
            layers, heads = x.shape[0], x.shape[-1]
    kv = 0
    head_dim = None
    for path, x in jax.tree_util.tree_flatten_with_path(pool)[0]:
        if _names(path)[-1] in ("k", "v"):
            # (layers, pages, page, Hkv, hd) or (pages, page, Hkv, hd)
            per_slot = int(np.prod(x.shape)) // (x.shape[-4] * x.shape[-3])
            kv += per_slot * x.dtype.itemsize
            head_dim = x.shape[-1]
    return ModelCounts(n, nbytes, layers, heads // head_dim, head_dim, kv)


def _attn_flops(m: ModelCounts, ctx: int) -> int:
    return 4 * m.layers * m.heads * m.head_dim * ctx


def round_flops(t: ModelCounts, d: ModelCounts, ctxs, gamma: int) -> int:
    """Operations of one round with active rows at context lengths ctxs."""
    p = gamma + 1
    return sum(p * (2 * t.matmul_params + _attn_flops(t, c))
               + p * (2 * d.matmul_params + _attn_flops(d, c)) for c in ctxs)


def round_bytes(t: ModelCounts, d: ModelCounts, ctxs, gamma: int) -> int:
    """Bytes one round needs to move with active rows at lengths ctxs."""
    p = gamma + 1
    live = sum(ctxs)
    return (t.matmul_bytes + p * d.matmul_bytes
            + live * (t.kv_bytes_per_token + p * d.kv_bytes_per_token))


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
