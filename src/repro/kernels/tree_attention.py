"""Tree-attention Pallas kernel: score all draft-tree nodes in one pass.

Tree-speculative verification runs the target over N tree nodes against a
long KV cache — the same memory-bound regime as flash-decode, but with N
query rows per (batch, kv-head) whose validity is an *ancestor mask* (each
node sees its root path plus the committed prefix) instead of plain
causality. The kernel streams KV tiles HBM->VMEM with online-softmax
accumulation, grid (batch, kv_head, kv_tiles); the kv-tile axis is
minor/sequential so the per-row accumulators carry across tiles.

GQA layout: q (B, Hkv, N, G, hd) with G = num_heads // num_kv_heads. The
wrapper flattens each (batch, kv-head)'s queries to R = N*G rows, so every
grid step is one (R, hd) x (hd, St) score matmul and one (R, St) x (St, hd)
value matmul. Flash-decode is the N = 1 case of the same kernel.

Block layout (the TPU's (8, 128) tiling applies to a block's last two
dims): the wrapper moves K/V to (B, Hkv, S, hd) so a KV block is an
(St, hd) tile, and expands the mask to one int32 row per query row,
(B, R, S), so a mask block is an (R, St) tile. Row statistics live in
(R, 1) scratch.

The ancestor/validity mask arrives precomputed as (B, N, S) bool
(``spectree.tree.tree_attn_mask`` ANDed with slot occupancy) — tree
bookkeeping stays outside the kernel, like position bookkeeping does for
flash-decode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KV_TILE = 128


def kv_window(n: int, page: int = 1) -> int:
    """Smallest KV width >= ``n``, a whole number of ``page``-slot pages,
    that the kernel tiles: up to one tile any width (the block is then the
    whole axis), beyond it a multiple of ``KV_TILE``."""
    w = -(-n // page) * page
    if w <= KV_TILE:
        return w
    step = math.lcm(page, KV_TILE)
    return -(-n // step) * step


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref,
                 acc_scr, m_scr, l_scr, *, n_tiles, scale, softcap):
    tidx = pl.program_id(2)

    @pl.when(tidx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (St, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (St, hd)
    mask = mask_ref[0] != 0                              # (R, St)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (R, St)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(tidx == n_tiles - 1)
    def _done():
        out_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                         ).astype(out_ref.dtype)


def masked_attention(q, k, v, mask, softcap=None, interpret=True):
    """q: (B, Hkv, R, hd) query rows; k/v: (B, S, Hkv, hd); mask: (B, R, S).

    Returns (B, Hkv, R, hd) fp32: each query row attends exactly the slots
    its mask row allows (rows are shared by every kv head)."""
    B, Hkv, R, hd = q.shape
    S = k.shape[1]
    st = min(KV_TILE, S)
    assert S % st == 0, (S, st)
    grid = (B, Hkv, S // st)
    kt = jnp.swapaxes(k, 1, 2)                           # (B, Hkv, S, hd)
    vt = jnp.swapaxes(v, 1, 2)
    return pl.pallas_call(
        functools.partial(_attn_kernel, n_tiles=grid[2],
                          scale=1.0 / math.sqrt(hd), softcap=softcap),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, R, hd), lambda b, h, s: (b, h, 0, 0)),
                  pl.BlockSpec((1, 1, st, hd), lambda b, h, s: (b, h, s, 0)),
                  pl.BlockSpec((1, 1, st, hd), lambda b, h, s: (b, h, s, 0)),
                  pl.BlockSpec((1, R, st), lambda b, h, s: (b, 0, s))],
        out_specs=pl.BlockSpec((1, 1, R, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((R, hd), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32)],
        interpret=interpret,
    )(q, kt, vt, mask.astype(jnp.int32))


def tree_attention(q, k, v, mask, softcap=None, interpret=True):
    """q: (B, Hkv, N, G, hd); k/v: (B, S, Hkv, hd); mask: (B, N, S) bool.

    Returns (B, Hkv, N, G, hd) fp32 attention output — one row per tree
    node, each attending exactly the slots its mask row allows (ancestors +
    committed prefix).
    """
    B, Hkv, N, G, hd = q.shape
    rows = jnp.repeat(mask, G, axis=1)                   # row n*G+g <- node n
    out = masked_attention(q.reshape(B, Hkv, N * G, hd), k, v, rows,
                           softcap=softcap, interpret=interpret)
    return out.reshape(B, Hkv, N, G, hd)
