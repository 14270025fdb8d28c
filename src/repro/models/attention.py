"""GQA attention: training (chunked causal), prefill, and single-token decode.

Variants required by the assigned architectures:
  - grouped-query attention (all archs; kv heads <= q heads)
  - RoPE (theta per config)
  - attention-logit softcapping (gemma2)
  - sliding-window / local attention (gemma2 local layers; long-context mode)
  - qk-norm (optional)

Training/prefill attention is *query-chunked* (``cfg.attn_chunk``): a
``lax.scan`` over query chunks bounds the materialized score tensor to
(B, H, C, S) — the pure-JAX analogue of flash attention's memory behaviour,
and what makes the 32k-prefill dry-run memory-sane. The Pallas flash-decode
kernel (repro.kernels) is an optional fast path for the decode step.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import (dense_param, softcap, apply_rope, init_rms_norm,
                     matmul_param, rms_norm)
from ..sharding import context as shctx

NEG_INF = -2.0e38

#: tree-attention fast-path override: None = auto (compiled Pallas only,
#: i.e. whenever kernels.ops.INTERPRET is False), True/False = force.
TREE_FASTPATH = None

#: paged-attention kernel override for eligible calls (``_use_paged_kernel``):
#: None = auto (compiled Pallas only), True/False = force.
PAGED_FASTPATH = None


def _opt_seq_shard(q, k, v, cfg):
    """Optimized-profile fix (§Perf it.1, phi4-class archs): when num_heads
    does not divide the model axis, GSPMD's fallback for head-sharded
    attention all-reduces the full (B,H,C,S) score tensor per query chunk
    (measured: 6.4 GB x 64 chunks x 32 layers on phi4 prefill_32k). Instead,
    constrain K/V to be *sequence-sharded* over the model axis: scores are
    computed locally per KV shard, the distributed softmax exchanges only
    (B,H,C) max/sum stats, and the PV contraction all-reduces just the
    (B,H,C,hd) outputs."""
    mesh = shctx.get_mesh()
    if mesh is None or not shctx.optimized():
        return q, k, v
    maxis = shctx.model_axis()
    msize = mesh.shape[maxis]
    if cfg.num_heads % msize == 0 or k.shape[1] % msize != 0:
        return q, k, v                       # head sharding works / S odd
    daxes = shctx.data_axes()
    b = daxes if q.shape[0] % _prod(mesh, daxes) == 0 else ()
    q = shctx.maybe_constraint(q, b, None, None, None)
    k = shctx.maybe_constraint(k, b, maxis, None, None)
    v = shctx.maybe_constraint(v, b, maxis, None, None)
    return q, k, v


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def init_attention(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.head_dim_
    kq, kk, kv, ko = jax.random.split(key, 4)
    wq, sq = dense_param(kq, d, cfg.num_heads * hd, dtype, "fsdp", "tp")
    wk, sk = dense_param(kk, d, cfg.num_kv_heads * hd, dtype, "fsdp", "tp")
    wv, sv = dense_param(kv, d, cfg.num_kv_heads * hd, dtype, "fsdp", "tp")
    wo, so = dense_param(ko, cfg.num_heads * hd, d, dtype, "tp", "fsdp")
    params = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    specs = {"wq": sq, "wk": sk, "wv": sv, "wo": so}
    if cfg.qk_norm:
        params["q_norm"], specs["q_norm"] = init_rms_norm(hd)
        params["k_norm"], specs["k_norm"] = init_rms_norm(hd)
    return params, specs


def _project_qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q = matmul_param(x, params["wq"])
    k = matmul_param(x, params["wk"])
    v = matmul_param(x, params["wv"])
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if S > 1:   # not the decode path: pin layouts before RoPE (SPerf it.2 —
                # constraining after RoPE forced GSPMD full-remat copies)
        q, k, v = _opt_seq_shard(q, k, v, cfg)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg):
    """q: (B, Sq, H, hd), k/v: (B, Skv, Hkv, hd), mask: (Sq, Skv) or (B,Sq,Skv)."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = softcap(scores, cfg.attn_softcap)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def _use_tree_kernel(S: int) -> bool:
    """Dispatch the Pallas tree-attention kernel for tree-masked decode?

    Auto mode uses it whenever Pallas compiles natively (in interpret mode
    the pure-JAX ``_sdpa`` is strictly faster). The KV width must then tile
    (``kernels.tree_attention.kv_window`` — the engines size their windows
    with it); a width that does not raises instead of quietly measuring or
    equivalence-testing ``_sdpa``. ``TREE_FASTPATH`` True/False forces the
    choice."""
    from ..kernels import ops, tree_attention as tk
    if TREE_FASTPATH is not None:
        return TREE_FASTPATH
    if ops.INTERPRET:
        return False
    if tk.kv_window(S) != S:
        raise ValueError(
            f"tree verify over a {S}-slot KV window: the tree-attention "
            f"kernel tiles widths up to {tk.KV_TILE} or multiples of it; "
            "size the cache with kernels.tree_attention.kv_window")
    return True


def _tree_attend(q, k, v, mask, cfg):
    """Tree-verify fast path: q (B, T, H, hd), k/v (B, S, Hkv, hd),
    mask (B, T, S) -> (B, T, H, hd). One kernel launch scores all T tree
    nodes (kernels.tree_attention; oracle ref.ref_tree_attention)."""
    from ..kernels import ops
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, hd).transpose(0, 2, 1, 3, 4)
    out = ops.tree_verify_attention(qg, k, v, mask, softcap=cfg.attn_softcap)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, T, H, hd).astype(q.dtype)


def _attend(q, k, v, mask, cfg, tree: bool):
    """Masked decode attention over a gathered fp cache view; tree-masked
    calls go through the Pallas tree kernel when eligible."""
    if tree and _use_tree_kernel(k.shape[1]):
        return _tree_attend(q, k, v, mask, cfg)
    return _sdpa(q, k, v, mask, cfg)


def causal_attention(params, x, positions, cfg, window: Optional[int] = None):
    """Full-sequence causal attention, scanned over query chunks."""
    B, S, D = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    C = min(cfg.attn_chunk, S)
    if S % C != 0:  # fall back to one chunk for odd smoke-test lengths
        C = S
    n_chunks = S // C
    kv_pos = positions  # (B, S) or (S,)
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos[None], (B, S))

    def chunk(carry, idx):
        qc = jax.lax.dynamic_slice_in_dim(q, idx * C, C, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(kv_pos, idx * C, C, axis=1)  # (B, C)
        m = qp[:, :, None] >= kv_pos[:, None, :]                       # causal
        if window is not None:
            m &= kv_pos[:, None, :] > qp[:, :, None] - window
        return carry, _sdpa(qc, k, v, m, cfg)

    _, outs = jax.lax.scan(chunk, None, jnp.arange(n_chunks))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, cfg.num_heads, cfg.head_dim_)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim_)
    return matmul_param(out, params["wo"])


def decode_attention(params, x, cache, pos, cfg,
                     window: Optional[int] = None, slots=None, attn_mask=None):
    """One-step decode with a (possibly ring-buffer) KV cache.

    x: (B, T, D) new tokens (T = 1, gamma+1 during speculative verify, or the
      tree-node count during tree speculation)
    cache: {"k": (B, Smax, Hkv, hd), "v": same, "pos": (B, Smax)} where "pos"
      holds absolute positions already written (-1 for empty slots).
    pos: (B, T) positions of x (RoPE positions).
    slots: optional (B, T) *storage* positions overriding ``pos`` for cache
      insertion — tree speculation stores sibling nodes (same RoPE position)
      at distinct slots. "pos" then records the storage position, so rewinds
      keyed on it stay exact.
    attn_mask: optional (B, T, Smax) slot-aligned mask replacing positional
      causality (tree ancestor masks); validity (written slots) and the
      sliding window are still enforced here. Tree-masked calls dispatch the
      Pallas tree-attention kernel when eligible (``_use_tree_kernel``).

    A cache carrying "k_scale"/"v_scale" leaves (repro.quant.kvcache) is an
    int8 cache: new entries are absmax-quantized per (slot, kv-head) on
    write, and the read view is dequantized on the fly — only int8 bytes
    plus scale vectors live in (and stream from) the cache.
    Returns (out, cache) with the new tokens inserted.
    """
    B, T, D = x.shape
    kcache, vcache, cache_pos = cache["k"], cache["v"], cache["pos"]
    kv_quant = "k_scale" in cache
    Smax = kcache.shape[1]
    q, k, v = _project_qkv(params, x, cfg, pos)
    # ring-buffer insertion: slot = position % Smax (full cache: Smax >= pos)
    write_pos = pos if slots is None else slots
    slot_idx = (write_pos % Smax).astype(jnp.int32)            # (B, T)
    bidx = jnp.arange(B)[:, None]
    new_cache = {}
    if kv_quant:
        from ..quant.kvcache import dequantize_kv_entry, quantize_kv_entry
        kq, ks = quantize_kv_entry(k)
        vq, vs = quantize_kv_entry(v)
        kcache = kcache.at[bidx, slot_idx].set(kq)
        vcache = vcache.at[bidx, slot_idx].set(vq)
        k_scale = cache["k_scale"].at[bidx, slot_idx].set(ks)
        v_scale = cache["v_scale"].at[bidx, slot_idx].set(vs)
        kc = dequantize_kv_entry(kcache, k_scale, q.dtype)
        vc = dequantize_kv_entry(vcache, v_scale, q.dtype)
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    else:
        kcache = kcache.at[bidx, slot_idx].set(k.astype(kcache.dtype))
        vcache = vcache.at[bidx, slot_idx].set(v.astype(vcache.dtype))
        kc, vc = kcache.astype(q.dtype), vcache.astype(q.dtype)
    cache_pos = cache_pos.at[bidx, slot_idx].set(write_pos.astype(jnp.int32))
    # valid = written and causal (<= query position) and within window
    if attn_mask is None:
        m = ((cache_pos[:, None, :] >= 0)
             & (cache_pos[:, None, :] <= pos[:, :, None]))
    else:
        m = (cache_pos[:, None, :] >= 0) & attn_mask
    if window is not None:
        m &= cache_pos[:, None, :] > pos[:, :, None] - window
    out = _attend(q, kc, vc, m, cfg, tree=attn_mask is not None)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim_)
    out = matmul_param(out, params["wo"])
    new_cache.update(k=kcache, v=vcache, pos=cache_pos)
    return out, new_cache


def _model_shards() -> int:
    """Chips of the installed mesh's model axis (1 without a mesh)."""
    mesh, axis = shctx.get_mesh(), shctx.model_axis()
    return 1 if mesh is None or axis is None else mesh.shape[axis]


def _use_paged_kernel(kpool, attn_mask) -> bool:
    """Read the paged pool in place with the Pallas kernel
    (kernels.paged_attention) instead of gathering every row's span?

    Eligible: no slot mask (tree verify has its own kernel), a float pool
    (int8 pools dequantize in the gather) whose KV heads split evenly over
    the mesh's model axis and whose heads on one chip the kernel can load
    (``paged_attention.supports``). Auto mode then uses it whenever Pallas
    compiles natively; in interpret mode the gather is faster.
    ``PAGED_FASTPATH`` True/False forces the choice for eligible calls."""
    from ..kernels import ops, paged_attention as pk
    if attn_mask is not None or not jnp.issubdtype(kpool.dtype, jnp.floating):
        return False
    hkv, n = kpool.shape[-2], _model_shards()
    if hkv % n or not pk.supports(hkv // n, kpool.dtype):
        return False
    if PAGED_FASTPATH is not None:
        return PAGED_FASTPATH
    return not ops.INTERPRET


def _paged_kernel(q, kpool, vpool, page_pos, page_table, pos, layer,
                  softcap, window):
    """``ops.paged_attention``, on each chip over its own KV heads where a
    mesh splits the model: a Mosaic kernel cannot be partitioned
    automatically, so it runs under ``shard_map`` over the model axis with
    q and the output split on heads and the pools on KV heads (a chip's
    query heads are those of its KV heads), the page table, slot and query
    positions replicated. Attention needs no collective."""
    from ..kernels import ops

    def call(q, k, v, pp, table, qpos, layer):
        return ops.paged_attention(q, k, v, pp, table, qpos, layer,
                                   softcap=softcap, window=window)
    if _model_shards() == 1:
        return call(q, kpool, vpool, page_pos, page_table, pos, layer)
    from jax.sharding import PartitionSpec as P
    axis = shctx.model_axis()
    heads, pools = P(None, None, axis), P(None, None, None, axis)
    return jax.shard_map(
        call, mesh=shctx.get_mesh(),
        in_specs=(heads, pools, pools, P(), P(), P(), P()),
        out_specs=heads, check_vma=False,
    )(q, kpool, vpool, page_pos, page_table, pos,
      jnp.asarray(layer, jnp.int32))


def paged_decode_attention(params, x, cache, page_table, pos, cfg,
                           window: Optional[int] = None, slots=None,
                           attn_mask=None, layer=None):
    """Decode step against a shared paged KV pool.

    cache: {"k": (P, page, Hkv, hd), "v": same, "page_pos": (P, page)} — one
      physical pool shared by every sequence; "page_pos" holds the absolute
      position written into each pool slot (-1 = empty). With ``layer``
      given, every leaf carries the layer scan's leading axis (the stacked
      pools of all layers) and this call reads and writes entry ``layer``:
      the kernel then reads the scan's own buffer, with nothing sliced out.
    page_table: (B, max_pages) int32 mapping a row's logical page index
      (position // page) to a physical page id. Physical page 0 is reserved
      as a null/trash page: unallocated table entries point there, writes
      from masked-out rows land there, and reads through a 0 entry are
      force-masked — so page 0's contents never influence any output.
    pos: (B, T) absolute positions of the new tokens x (RoPE positions).
    slots: optional (B, T) storage positions overriding ``pos`` for the pool
      scatter (tree speculation: siblings share a position, not a slot);
      "page_pos" then records the storage position.
    attn_mask: optional (B, T, max_pages*page) mask over the gathered view
      replacing positional causality (column = storage position).

    Two read paths (``_use_paged_kernel``): the Pallas paged-attention
    kernel reads only each row's live pages in place; otherwise every
    mapped page of every row is gathered into a (B, max_pages*page) view
    and ``_attend`` scores all of it. Both apply the same validity mask.

    Pools carrying "k_scale"/"v_scale" (P, page, Hkv) leaves are int8
    (repro.quant.kvcache): entries are quantized per (page slot, kv head) on
    scatter and dequantized on gather, same convention as the dense cache.

    Prefix sharing (serving.prefix_cache): the same physical page may appear
    in several rows' tables. Reads need no special handling — the gathered
    per-row view is position-contiguous either way, and each query row's
    attention reduction depends only on the gathered values, not on which
    rows share them (this is what makes sharing bit-exact at temp 0). The
    contract is on *writes*: shared (refcount>1) pages are read-only; the
    allocator guarantees every scatter here targets pages private to the
    row, because shared pages hold only positions below the row's committed
    length and new tokens are always written at or above it (the one
    boundary case — resuming prefill inside the last shared page — is
    COWed to a private copy before the write).
    """
    if layer is None:
        cache = jax.tree.map(lambda a: a[None], cache)
        out, new_cache = paged_decode_attention(
            params, x, cache, page_table, pos, cfg, window, slots, attn_mask,
            layer=0)
        return out, jax.tree.map(lambda a: a[0], new_cache)
    B, T, D = x.shape
    kpool, vpool, page_pos = cache["k"], cache["v"], cache["page_pos"]
    kv_quant = "k_scale" in cache
    page = page_pos.shape[-1]
    max_pages = page_table.shape[1]
    q, k, v = _project_qkv(params, x, cfg, pos)
    # scatter new tokens through the page table
    write_pos = pos if slots is None else slots
    page_idx = jnp.clip(write_pos // page, 0, max_pages - 1)
    phys = jnp.take_along_axis(page_table, page_idx, axis=1)   # (B, T)
    off = (write_pos % page).astype(jnp.int32)
    W = max_pages * page
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    new_cache = {}
    if kv_quant:
        from ..quant.kvcache import dequantize_kv_entry, quantize_kv_entry
    # device scopes: the pool scatter, the per-row gather of every mapped
    # page (with its reshape and cast), and the attention over the view —
    # or, on the kernel path, the kernel's in-place reads under
    # ``sdpa/paged_kernel``
    with jax.named_scope("kv_write"):
        if kv_quant:
            kq, ks = quantize_kv_entry(k)
            vq, vs = quantize_kv_entry(v)
            kpool = kpool.at[layer, phys, off].set(kq)
            vpool = vpool.at[layer, phys, off].set(vq)
            k_scale = cache["k_scale"].at[layer, phys, off].set(ks)
            v_scale = cache["v_scale"].at[layer, phys, off].set(vs)
            new_cache.update(k_scale=k_scale, v_scale=v_scale)
        else:
            kpool = kpool.at[layer, phys, off].set(k.astype(kpool.dtype))
            vpool = vpool.at[layer, phys, off].set(v.astype(vpool.dtype))
        page_pos = page_pos.at[layer, phys, off].set(
            write_pos.astype(jnp.int32))
    if _use_paged_kernel(kpool, attn_mask):
        with jax.named_scope("sdpa"), jax.named_scope("paged_kernel"):
            out = _paged_kernel(q, kpool, vpool, page_pos, page_table, pos,
                                layer, cfg.attn_softcap,
                                window).astype(q.dtype)
    else:
        with jax.named_scope("kv_gather"):
            if kv_quant:
                kc = dequantize_kv_entry(
                    kpool[layer, page_table], k_scale[layer, page_table],
                    q.dtype).reshape(B, W, Hkv, hd)
                vc = dequantize_kv_entry(
                    vpool[layer, page_table], v_scale[layer, page_table],
                    q.dtype).reshape(B, W, Hkv, hd)
            else:
                kc = kpool[layer, page_table].reshape(B, W, Hkv, hd)
                vc = vpool[layer, page_table].reshape(B, W, Hkv, hd)
                kc, vc = kc.astype(q.dtype), vc.astype(q.dtype)
            cp = page_pos[layer, page_table]
        cp = jnp.where((page_table == 0)[:, :, None], -1, cp)
        cp = cp.reshape(B, W)
        if attn_mask is None:
            m = (cp[:, None, :] >= 0) & (cp[:, None, :] <= pos[:, :, None])
        else:
            m = (cp[:, None, :] >= 0) & attn_mask
        if window is not None:
            m &= cp[:, None, :] > pos[:, :, None] - window
        with jax.named_scope("sdpa"):
            out = _attend(q, kc, vc, m, cfg, tree=attn_mask is not None)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim_)
    out = matmul_param(out, params["wo"])
    new_cache.update(k=kpool, v=vpool, page_pos=page_pos)
    return out, new_cache


def prefill_attention(params, x, positions, cfg, cache_len: int,
                      window: Optional[int] = None):
    """Causal attention over the prompt, returning a KV cache of ``cache_len``."""
    B, S, D = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    kv_pos = positions if positions.ndim == 2 else jnp.broadcast_to(positions[None], (B, S))
    C = min(cfg.attn_chunk, S)
    if S % C != 0:
        C = S

    def chunk(carry, idx):
        qc = jax.lax.dynamic_slice_in_dim(q, idx * C, C, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(kv_pos, idx * C, C, axis=1)
        m = qp[:, :, None] >= kv_pos[:, None, :]
        if window is not None:
            m &= kv_pos[:, None, :] > qp[:, :, None] - window
        return carry, _sdpa(qc, k, v, m, cfg)

    _, outs = jax.lax.scan(chunk, None, jnp.arange(S // C))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, cfg.num_heads * cfg.head_dim_)
    out = matmul_param(out, params["wo"])

    # build cache (ring layout consistent with decode_attention)
    Smax = cache_len
    if S <= Smax:
        pad = Smax - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.compute_dtype)
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.compute_dtype)
        cp = jnp.pad(kv_pos.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=-1)
    else:  # keep the last Smax positions, placed at slot pos % Smax
        kc = jnp.zeros((B, Smax, cfg.num_kv_heads, cfg.head_dim_), cfg.compute_dtype)
        vc = jnp.zeros_like(kc)
        cp = jnp.full((B, Smax), -1, jnp.int32)
        keep = S - Smax
        slots = (kv_pos[:, keep:] % Smax).astype(jnp.int32)
        bidx = jnp.arange(B)[:, None]
        kc = kc.at[bidx, slots].set(k[:, keep:].astype(kc.dtype))
        vc = vc.at[bidx, slots].set(v[:, keep:].astype(vc.dtype))
        cp = cp.at[bidx, slots].set(kv_pos[:, keep:].astype(jnp.int32))
    return out, {"k": kc, "v": vc, "pos": cp}
