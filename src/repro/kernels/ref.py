"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are the *definitions*; the kernels must match them on shape/dtype
sweeps (tests/test_kernels_*.py). The distillation-loss oracles are shared
with repro.core.losses (the kernels exist to compute the same math without
HBM round-trips)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core import losses as L


def ref_logsumexp(x):
    return jax.nn.logsumexp(x.astype(jnp.float32), axis=-1)


def ref_loss_terms(s, t, mu, inv_sigma, mode="tvdpp"):
    """Per-row (loss, c, sum p*r, sum p*r^2) — mirrors kernels.loss_terms."""
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    q = jax.nn.softmax(t.astype(jnp.float32), -1)
    r = (q > p).astype(jnp.float32)
    r1 = jnp.sum(p * r, -1)
    r2 = jnp.sum(p * r * r, -1)
    if mode == "kld":
        lp = jax.nn.log_softmax(s.astype(jnp.float32), -1)
        lq = jax.nn.log_softmax(t.astype(jnp.float32), -1)
        loss = jnp.sum(q * (lq - lp), -1)
        c = jnp.zeros_like(loss)
    elif mode == "tvd":
        w = 0.5 * jnp.sign(p - q)
        c = jnp.sum(p * w, -1)
        loss = 0.5 * jnp.sum(jnp.abs(q - p), -1)
    elif mode == "tvdpp":
        w = -(r - mu) * inv_sigma
        c = jnp.sum(p * w, -1)
        loss = c
    else:
        raise ValueError(mode)
    return loss, c, r1, r2


def ref_loss_grad(s, t, c, g_rows, mu, inv_sigma, mode="tvdpp"):
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    q = jax.nn.softmax(t.astype(jnp.float32), -1)
    g = g_rows[:, None]
    if mode == "kld":
        return g * (p - q)
    if mode == "tvd":
        w = 0.5 * jnp.sign(p - q)
    else:
        w = -((q > p).astype(jnp.float32) - mu) * inv_sigma
    return g * p * (w - c[:, None])


def ref_distill_loss(mode, s, t, mask):
    """Scalar loss — equals repro.core.losses on the same inputs."""
    fn = {"tvdpp": L.tvdpp, "tvd": L.tvd, "kld": L.kld}[mode]
    return fn(s, t, mask)


def ref_flash_decode(q, k, v, mask, softcap=None):
    """q: (B, Hkv, G, hd); k/v: (B, S, Hkv, hd); mask: (B, S)."""
    B, Hkv, G, hd = q.shape
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))


def ref_dequant(q, scale, bits, group):
    """Quantized weight -> (K, N) fp32. int8: q (K,N) int8, scale (1,N);
    int4: q (K//2,N) uint8 packed (even K row = low nibble), scale
    (K//group, N)."""
    if bits == 8:
        return q.astype(jnp.float32) * scale
    lo = (q & 0xF).astype(jnp.int32)
    hi = ((q >> 4) & 0xF).astype(jnp.int32)
    lo = lo - 16 * (lo >= 8)
    hi = hi - 16 * (hi >= 8)
    half, n = q.shape
    vals = jnp.stack([lo, hi], 1).reshape(2 * half, n).astype(jnp.float32)
    return vals * jnp.repeat(scale, group, axis=0)


def ref_quant_matmul(x, q, scale, bits, group, pre=None):
    """The quant_matmul oracle: dequantize-then-matmul in fp32.

    x (M, K); returns (M, N) fp32. ``pre`` (K,) is the AWQ activation
    pre-scale (applied to x, matching the ops wrapper)."""
    x = x.astype(jnp.float32)
    if pre is not None:
        x = x * pre[None, :]
    return x @ ref_dequant(q, scale, bits, group)


def ref_tree_attention(q, k, v, mask, softcap=None):
    """q: (B, Hkv, N, G, hd); k/v: (B, S, Hkv, hd); mask: (B, N, S).

    Per-node masked attention — the oracle for kernels.tree_attention
    (tree-speculative verify: node n attends its ancestor set)."""
    B, Hkv, N, G, hd = q.shape
    s = jnp.einsum("bhngd,bshd->bhngs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhngs,bshd->bhngd", p, v.astype(jnp.float32))


def ref_paged_attention(q, k_pool, v_pool, page_pos, page_table, pos,
                        layer=0, softcap=None, window=None):
    """The gather path of ``models.attention.paged_decode_attention``: every
    row's whole table span gathered into a view, masked by the positions
    written there, softmaxed in float32. Oracle for kernels.paged_attention.

    q: (B, T, H, hd); k_pool/v_pool: (L, P, page, Hkv, hd); page_pos:
    (L, P, page); page_table: (B, max_pages), null page 0 masked; pos:
    (B, T). A query row with no valid slot (an inactive row) reads zeros."""
    B, T, H, hd = q.shape
    _, _, page, hkv, _ = k_pool.shape
    W = page_table.shape[1] * page
    k = k_pool[layer][page_table].reshape(B, W, hkv, hd).astype(jnp.float32)
    v = v_pool[layer][page_table].reshape(B, W, hkv, hd).astype(jnp.float32)
    cp = jnp.where((page_table == 0)[:, :, None], -1,
                   page_pos[layer][page_table]).reshape(B, W)
    m = (cp[:, None, :] >= 0) & (cp[:, None, :] <= pos[:, :, None])
    if window is not None:
        m &= cp[:, None, :] > pos[:, :, None] - window
    qg = q.reshape(B, T, hkv, H // hkv, hd).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->btkgs", qg, k) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(m[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", p, v)
    out = jnp.where(jnp.any(m, -1)[:, :, None, None, None], out, 0.0)
    return out.reshape(B, T, H, hd)
