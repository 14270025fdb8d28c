"""Fused distillation-loss Pallas kernels (TVD++ / TVD / KLD over vocab).

The fine-tuning hot spot (DESIGN.md §3): per token the loss reduces over the
full vocabulary (32k-256k) needing softmax(student), softmax(teacher), the
reward indicator, and a weighted reduction. Materializing both (N, V) fp32
probability tensors costs several HBM round-trips; these kernels stream the
vocab through VMEM tiles instead:

  kernel 1  row_logsumexp   — online max/sum-exp per row (one sweep).
  kernel 2  loss_terms      — given both rows' logsumexp stats, one sweep
                              computing the per-row loss and the softmax-
                              jacobian residual c = sum_x p*w (mode-specific).
  kernel 3  loss_grad       — one sweep emitting dL/d(student logits) from
                              the stats + residual (used by the custom VJP in
                              ops.py).

Grid layout: (row_tiles, vocab_tiles) with the vocab dimension minor — on TPU
the grid is executed sequentially over the last axis, so VMEM scratch
accumulators carry across vocab tiles (the canonical online-softmax pattern).
Tile sizes are MXU/VPU aligned: rows in multiples of 8 sublanes, vocab in
multiples of 128 lanes. Per-row statistics travel as (N, 1) columns (a
rank-1 (Rt,) block does not tile on the TPU) and per-call scalars as (1, 1);
the public wrappers take and return (N,) vectors and () scalars.

Per-element weights w (so that grad = p * (w - c), c = sum p*w):
  tvdpp : w = -adv,            adv = sg[(r - mu) / sigma], r = 1{q > p}
  tvd   : w = 0.5 * sign(p - q)
  kld   : handled closed-form in the grad kernel (dL/ds = p - q).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

ROW_TILE = 8
VOCAB_TILE = 512


def _pick_tile(n: int, pref: int) -> int:
    """Largest power-of-two tile <= pref that divides n (fallback n)."""
    t = pref
    while t > 1:
        if n % t == 0:
            return t
        t //= 2
    return n


# ----------------------------------------------------------- 1: logsumexp

def _lse_kernel(x_ref, out_ref, m_scr, l_scr, *, n_vtiles):
    vidx = pl.program_id(1)

    @pl.when(vidx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    x = x_ref[...].astype(jnp.float32)                    # (Rt, Vt)
    m_new = jnp.maximum(m_scr[...], jnp.max(x, axis=1, keepdims=True))
    l_scr[...] = l_scr[...] * jnp.exp(m_scr[...] - m_new) + \
        jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(vidx == n_vtiles - 1)
    def _done():
        out_ref[...] = m_scr[...] + jnp.log(l_scr[...])


def _tiles(N, V):
    rt, vt = _pick_tile(N, ROW_TILE), _pick_tile(V, VOCAB_TILE)
    return rt, vt, (N // rt, V // vt)


def _col(x):
    """(N,) -> (N, 1) fp32 column."""
    return x.astype(jnp.float32).reshape(-1, 1)


def _scalar(x):
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


def row_logsumexp(x, interpret=True):
    N, V = x.shape
    rt, vt, grid = _tiles(N, V)
    out = pl.pallas_call(
        functools.partial(_lse_kernel, n_vtiles=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((rt, vt), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((rt, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return out[:, 0]


# ----------------------------------------------------------- per-mode weight

def _weight(mode, p, q, mu, inv_sigma):
    if mode == "tvdpp":
        r = (q > p).astype(jnp.float32)
        return -(r - mu) * inv_sigma
    if mode == "tvd":
        return 0.5 * jnp.sign(p - q)
    raise ValueError(mode)


def _probs(x_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)
    return jnp.exp(x - lse_ref[...])


# ----------------------------------------------------------- 2: loss terms

def _terms_kernel(s_ref, t_ref, lse_s_ref, lse_t_ref, mu_ref, isg_ref,
                  loss_ref, c_ref, r1_ref, r2_ref, *, mode):
    vidx = pl.program_id(1)
    outs = (loss_ref, c_ref, r1_ref, r2_ref)

    # the output blocks stay resident across the vocab axis: accumulate there
    @pl.when(vidx == 0)
    def _init():
        for o in outs:
            o[...] = jnp.zeros_like(o)

    p = _probs(s_ref, lse_s_ref)
    q = _probs(t_ref, lse_t_ref)
    if mode == "kld":
        lq = t_ref[...].astype(jnp.float32) - lse_t_ref[...]
        lp = s_ref[...].astype(jnp.float32) - lse_s_ref[...]
        loss_part = jnp.sum(q * (lq - lp), axis=1, keepdims=True)
        c_part = jnp.zeros_like(loss_part)
    else:
        w = _weight(mode, p, q, mu_ref[...], isg_ref[...])
        c_part = jnp.sum(p * w, axis=1, keepdims=True)
        if mode == "tvdpp":
            loss_part = c_part                      # L_row = sum p*(-adv) = c
        else:
            loss_part = jnp.sum(0.5 * jnp.abs(q - p), axis=1, keepdims=True)
    r = (q > p).astype(jnp.float32)
    loss_ref[...] += loss_part
    c_ref[...] += c_part
    r1_ref[...] += jnp.sum(p * r, axis=1, keepdims=True)
    r2_ref[...] += jnp.sum(p * r * r, axis=1, keepdims=True)


def loss_terms(s, t, lse_s, lse_t, mu, inv_sigma, mode="tvdpp", interpret=True):
    """-> per-row (loss, c, sum p*r, sum p*r^2)."""
    N, V = s.shape
    rt, vt, grid = _tiles(N, V)
    tile = pl.BlockSpec((rt, vt), lambda i, j: (i, j))
    col = pl.BlockSpec((rt, 1), lambda i, j: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    out = pl.pallas_call(
        functools.partial(_terms_kernel, mode=mode),
        grid=grid,
        in_specs=[tile, tile, col, col, one, one],
        out_specs=[col] * 4,
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 4,
        interpret=interpret,
    )(s, t, _col(lse_s), _col(lse_t), _scalar(mu), _scalar(inv_sigma))
    return tuple(o[:, 0] for o in out)


# ----------------------------------------------------------- 3: gradient

def _grad_kernel(s_ref, t_ref, lse_s_ref, lse_t_ref, c_ref, mu_ref, isg_ref,
                 g_ref, out_ref, *, mode):
    p = _probs(s_ref, lse_s_ref)
    q = _probs(t_ref, lse_t_ref)
    g = g_ref[...]                                        # (Rt, 1)
    if mode == "kld":
        out_ref[...] = g * (p - q)
    else:
        w = _weight(mode, p, q, mu_ref[...], isg_ref[...])
        out_ref[...] = g * p * (w - c_ref[...])


def loss_grad(s, t, lse_s, lse_t, c, g_rows, mu, inv_sigma, mode="tvdpp",
              interpret=True):
    """-> dL/ds (N, V) fp32, given upstream per-row cotangents g_rows."""
    N, V = s.shape
    rt, vt, grid = _tiles(N, V)
    tile = pl.BlockSpec((rt, vt), lambda i, j: (i, j))
    col = pl.BlockSpec((rt, 1), lambda i, j: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_grad_kernel, mode=mode),
        grid=grid,
        in_specs=[tile, tile, col, col, col, one, one, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((N, V), jnp.float32),
        interpret=interpret,
    )(s, t, _col(lse_s), _col(lse_t), _col(c), _scalar(mu),
      _scalar(inv_sigma), _col(g_rows))
