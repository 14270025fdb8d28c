"""Flash-decode attention (single-token decode, long KV cache).

SD verification and plain decode run the target over a KV cache of up to 512k
positions — purely memory-bound. One decode position is the one-node case
of the tree-attention kernel (kernels.tree_attention): KV tiles stream
HBM->VMEM with online-softmax accumulation, grid (batch, kv_head, kv_tiles),
and each grid step does a (G, hd) x (hd, St) score matmul and a
(G, St) x (St, hd) value matmul with G = num_heads // num_kv_heads.

Validity (causal + ring-buffer occupancy + sliding window) arrives as a
precomputed bool mask (B, S) — position bookkeeping stays outside the kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from .tree_attention import masked_attention


def flash_decode(q, k, v, mask, softcap=None, interpret=True):
    """q: (B, Hkv, G, hd); k/v: (B, S, Hkv, hd); mask: (B, S) bool.

    Returns (B, Hkv, G, hd) fp32 attention output for one decode position.
    """
    G = q.shape[2]
    rows = jnp.broadcast_to(mask[:, None, :], (mask.shape[0], G, mask.shape[1]))
    return masked_attention(q, k, v, rows, softcap=softcap,
                            interpret=interpret)
