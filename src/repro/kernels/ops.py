"""Jit'd public wrappers over the Pallas kernels, with custom VJPs.

``fused_distill_loss`` is a drop-in replacement for the reference losses in
repro.core.losses (same scalar value, same student gradient; the teacher is
frozen so its cotangent is zero).

``INTERPRET`` selects Pallas interpret mode (CPU emulation) vs compiled
Mosaic. It is resolved lazily on first use (reading it at import would
initialize the JAX backend as an import side effect): the
``REPRO_PALLAS_INTERPRET`` env var ("0"/"false" or "1"/"true") wins; unset,
it is interpret on the CPU backend and compiled on every other backend — a
backend that cannot compile the kernels fails loudly instead of falling
back to the interpreter.
Assigning ``ops.INTERPRET = ...`` still force-overrides it.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from . import distill_loss as dk
from . import flash_decode as fk
from . import paged_attention as pk
from . import quant_matmul as qk
from . import tree_attention as tk


def _env_interpret() -> bool:
    v = os.environ.get("REPRO_PALLAS_INTERPRET")
    if v is not None:
        return v.strip().lower() not in ("0", "false", "no", "off")
    return jax.default_backend() == "cpu"


def _interpret() -> bool:
    """Resolve ``INTERPRET`` on first use and cache it as the module
    global (so reads and ``ops.INTERPRET = ...`` overrides stay in sync)."""
    if "INTERPRET" not in globals():
        globals()["INTERPRET"] = _env_interpret()
    return globals()["INTERPRET"]


def __getattr__(name):          # PEP 562: lazy ``ops.INTERPRET`` attribute
    if name == "INTERPRET":
        return _interpret()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ------------------------------------------------------ fused distill loss

@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core_loss(s, t, mask, mu, inv_sigma, mode):
    loss, *_ = _core_fwd(s, t, mask, mu, inv_sigma, mode)
    return loss


def _core_fwd(s, t, mask, mu, inv_sigma, mode):
    lse_s = dk.row_logsumexp(s, interpret=_interpret())
    lse_t = dk.row_logsumexp(t, interpret=_interpret())
    loss_rows, c, _, _ = dk.loss_terms(s, t, lse_s, lse_t, mu, inv_sigma,
                                       mode=mode, interpret=_interpret())
    n = jnp.maximum(mask.sum(), 1.0)
    loss = (loss_rows * mask).sum() / n
    return loss, (s, t, lse_s, lse_t, c, mask, mu, inv_sigma, n)


def _core_bwd(mode, res, g):
    s, t, lse_s, lse_t, c, mask, mu, inv_sigma, n = res
    g_rows = (g * mask / n).astype(jnp.float32)
    ds = dk.loss_grad(s, t, lse_s, lse_t, c, g_rows, mu, inv_sigma,
                      mode=mode, interpret=_interpret())
    return (ds.astype(s.dtype), jnp.zeros_like(t), jnp.zeros_like(mask),
            jnp.zeros_like(mu), jnp.zeros_like(inv_sigma))


_core_loss.defvjp(_core_fwd, _core_bwd)


def fused_distill_loss(mode: str, s_logits, t_logits, mask):
    """Scalar distillation loss via Pallas kernels.

    s_logits/t_logits: (N, V); mask: (N,) float. For tvdpp the global
    p-weighted reward moments (paper Eq. 1 normalization) are computed by a
    first kernel sweep and treated as constants (stop-gradient), exactly like
    the reference implementation.
    """
    s = s_logits.astype(jnp.float32)
    t = t_logits.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    zero, one = jnp.zeros(()), jnp.ones(())
    if mode == "tvdpp":
        lse_s = dk.row_logsumexp(jax.lax.stop_gradient(s), interpret=_interpret())
        lse_t = dk.row_logsumexp(t, interpret=_interpret())
        _, _, r1, r2 = dk.loss_terms(jax.lax.stop_gradient(s), t, lse_s, lse_t,
                                     zero, one, mode="tvdpp", interpret=_interpret())
        n = jnp.maximum(mask.sum(), 1.0)
        mu = (r1 * mask).sum() / n
        var = (r2 * mask).sum() / n - mu * mu
        inv_sigma = jax.lax.rsqrt(jnp.maximum(var, 1e-12) + 1e-6)
        mu, inv_sigma = jax.lax.stop_gradient((mu, inv_sigma))
    else:
        mu, inv_sigma = zero, one
    return _core_loss(s, t, mask, mu, inv_sigma, mode)


# ------------------------------------------------------ flash decode

def flash_decode_attention(q, k, v, mask, softcap=None):
    """See kernels.flash_decode.flash_decode; ref oracle in kernels.ref."""
    return fk.flash_decode(q, k, v, mask, softcap=softcap, interpret=_interpret())


# ------------------------------------------------------ quant matmul

def dequant_matmul(x, qw):
    """Fused dequantize-matmul; see kernels.quant_matmul, oracle in
    kernels.ref.ref_quant_matmul.

    x (..., K) @ QWeight (K, N) -> (..., N) fp32. The AWQ activation
    pre-scale (one elementwise multiply) is applied here; the in-kernel work
    is the tile dequantize fused with the MXU contraction, so only
    int8/int4 bytes (+ scales) move from HBM.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    xm = x.reshape(-1, K)
    if qw.pre is not None:
        xm = xm * qw.pre[None, :].astype(xm.dtype)
    out = qk.quant_matmul(xm, qw.q, qw.scale, bits=qw.bits, group=qw.group,
                          interpret=_interpret())
    return out.reshape(lead + (qw.out_dim,))


# ------------------------------------------------------ tree attention

def tree_verify_attention(q, k, v, mask, softcap=None):
    """See kernels.tree_attention.tree_attention; oracle in kernels.ref.

    q (B, Hkv, N, G, hd), k/v (B, S, Hkv, hd), mask (B, N, S) — scores every
    tree node of a speculative draft tree in one kernel launch."""
    return tk.tree_attention(q, k, v, mask, softcap=softcap,
                             interpret=_interpret())


# ------------------------------------------------------ paged attention

def paged_attention(q, k_pool, v_pool, page_pos, page_table, pos, layer=0,
                    softcap=None, window=None):
    """See kernels.paged_attention.paged_attention; oracle in kernels.ref.

    q (B, T, H, hd) over stacked (L, P, page, Hkv, hd) pools read in place
    through ``page_table``: only each row's live pages move from HBM."""
    return pk.paged_attention(q, k_pool, v_pool, page_pos, page_table, pos,
                              layer, softcap=softcap, window=window,
                              interpret=_interpret())
