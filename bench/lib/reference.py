"""Plain float32 reference of the dense GQA / RoPE / SwiGLU decoder.

Written in plain ``jax.numpy`` from the layer equations, independent of the
program under test: it imports nothing of it and regenerates its weights
from the seed (``draw``), one layer at a time, so that it fits on the chip
beside nothing else. Every matmul runs at ``Precision.HIGHEST``.

Block (pre-norm, RMS norm applied as x * rsqrt(mean(x^2) + eps) * (1 + w)):
  h = norm1(x); q, k, v = h Wq, h Wk, h Wv; rotate-half RoPE on q and k over
  all head dims (base ``rope_theta``); causal softmax(q k^T / sqrt(hd)) v,
  query head j reading kv head j // (H / Hkv); x += o Wo;
  h = norm2(x); x += (silu(h Wg) * (h Wu)) Wd.
Output: final norm, then logits = h W_head (W_head = embedding^T if tied).

``fp8=True`` is the control: every matmul weight is rounded to
float8_e4m3fn with one scale per output channel, and every matmul input to
float8_e4m3fn with one scale per row, before the float32 product.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .draw import LAYER_LEAVES, draw

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
BUCKET = 1024          # sequences are padded to a multiple of this
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    if fp8:
        x = _q8(x, -1)
    return jnp.einsum("sd,df->sf", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _served(a):
    """The value the server holds: the draw rounded to bfloat16."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


class RefModel:
    """Reference for one model (target or drafter) of a configuration."""

    def __init__(self, cfg: dict, tag: str, fp8: bool = False):
        self.cfg, self.tag, self.fp8 = cfg, tag, fp8
        self._layer_w = jax.jit(self._layer_weights)
        self._block = jax.jit(self._block_fn)
        self._head = jax.jit(self._head_fn)
        self._final = jax.jit(self._final_fn)
        self._table = jax.jit(self._table_fn, static_argnums=1)

    # ------------------------------------------------------------ weights
    def _maybe_q(self, name, w):
        if self.fp8 and w.ndim == 2:
            return _q8(w, 0)
        return w

    def _layer_weights(self, key, layer):
        return {n: self._maybe_q(n, _served(draw(key, self.cfg, self.tag, n,
                                                 layer)))
                for n in LAYER_LEAVES}

    def _table_fn(self, key, name):
        return _served(draw(key, self.cfg, self.tag, name))

    # ------------------------------------------------------------ forward
    def _block_fn(self, x, w, pos, valid):
        c, q8 = self.cfg, self.fp8
        S = x.shape[0]
        H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        g = H // KV
        h = _rms(x, w["norm1"], c["norm_eps"])
        q = _mm(h, w["attn.wq"], q8).reshape(S, H, hd)
        k = _mm(h, w["attn.wk"], q8).reshape(S, KV, hd)
        v = _mm(h, w["attn.wv"], q8).reshape(S, KV, hd)
        q = _rope(q, pos, c["rope_theta"]).reshape(S, KV, g, hd)
        k = _rope(k, pos, c["rope_theta"])

        def qblock(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
            pb = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK, 0)
            s = jnp.einsum("qkgh,skh->kgqs", qb, k, precision=HI) / np.sqrt(hd)
            m = (pos[None, :] <= pb[:, None]) & valid[None, :]
            p = jax.nn.softmax(jnp.where(m, s, -jnp.inf), -1)
            return jnp.einsum("kgqs,skh->qkgh", p, v,
                              precision=HI).reshape(Q_BLOCK, H * hd)

        o = jax.lax.map(qblock, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
        x = x + _mm(o, w["attn.wo"], q8)
        h = _rms(x, w["norm2"], c["norm_eps"])
        ff = jax.nn.silu(_mm(h, w["mlp.w_gate"], q8)) * _mm(h, w["mlp.w_up"], q8)
        return x + _mm(ff, w["mlp.w_down"], q8)

    def _head_fn(self, h, head):
        return _mm(h, head, self.fp8)

    def _final_fn(self, x, rows, final_norm):
        return _rms(x[rows], final_norm, self.cfg["norm_eps"])

    def head(self, key):
        """The LM-head matrix (d_model, vocab) as this reference uses it."""
        if self.cfg["tie_embeddings"]:
            return self._maybe_q("head", self._table(key, "embed").T)
        return self._maybe_q("head", self._table(key, "lm_head"))

    def hidden(self, key, seqs, want):
        """seqs: list of 1-D int token arrays; want: list of int arrays of
        positions (indices into the matching sequence). Returns, per
        sequence, the final-norm hidden states (len(want_i), d_model) at the
        wanted positions; ``logits`` turns them into next-token logits."""
        c = self.cfg
        embed = self._table(key, "embed")
        xs, poss, valids = [], [], []
        for s in seqs:
            n = len(s)
            S = -(-n // BUCKET) * BUCKET
            toks = np.zeros((S,), np.int32)
            toks[:n] = s
            xs.append(embed[jnp.asarray(toks)])
            poss.append(jnp.asarray(np.arange(S, dtype=np.int32)))
            valids.append(jnp.asarray(np.arange(S) < n))
        del embed
        for layer in range(c["num_layers"]):
            w = self._layer_w(key, jnp.int32(layer))
            xs = [self._block(x, w, p, v) for x, p, v in zip(xs, poss, valids)]
        fn = _served(draw(key, c, self.tag, "final_norm"))
        return [self._final(x, jnp.asarray(np.asarray(r, np.int32)), fn)
                for x, r in zip(xs, want)]

    def logits(self, head, h):
        return self._head(h, head)
