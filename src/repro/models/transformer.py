"""Composable decoder stack over heterogeneous layer patterns.

A model is ``embed -> scan(groups) -> remainder -> final_norm -> lm_head``
where a *group* is one repetition of ``cfg.layer_pattern`` (e.g. ``(attn,)``
for dense, ``(local_attn, attn)`` for gemma2, ``(mamba x6, shared_attn)`` for
zamba2, ``(mlstm, slstm)`` for xlstm). Group params are stacked on a leading
axis and driven by ``jax.lax.scan`` so HLO size is O(1) in depth — this keeps
the 40-combo x 2-mesh dry-run compilable and is what a real deployment wants.

Three execution modes share the block definitions:
  train    — full-sequence, no cache (chunked-causal attention, chunked SSD)
  prefill  — full-sequence, emits a decode cache
  decode   — T new tokens (T=1, or gamma+1 in speculative verify) + cache

zamba2's shared attention blocks have *shared weights* (``num_shared_attn_sets``
sets used round-robin) but per-application caches; weights ride in the scan
closure, caches in the scanned xs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import (ATTN, LOCAL_ATTN, MAMBA, MLSTM, SLSTM, SHARED_ATTN)
from . import attention as attn_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (init_rms_norm, rms_norm, init_swiglu, swiglu,
                     init_embedding, embed_tokens, init_lm_head,
                     lm_head_logits)
from .moe import init_moe, moe_ffn

_ATTN_KINDS = (ATTN, LOCAL_ATTN, SHARED_ATTN)


def _has_ffn(cfg, kind):
    # zamba2's shared blocks are attention+MLP; plain MAMBA/MLSTM/SLSTM
    # blocks carry their FFN inside the block (or have none, xlstm d_ff=0).
    return kind in (ATTN, LOCAL_ATTN, SHARED_ATTN) and (cfg.d_ff > 0 or cfg.is_moe)


# ---------------------------------------------------------------- block init

def init_block(key, cfg, kind, dtype):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["norm1"], s["norm1"] = init_rms_norm(cfg.d_model)
    if kind in _ATTN_KINDS:
        p["attn"], s["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
    elif kind == MAMBA:
        p["mamba"], s["mamba"] = ssm_mod.init_mamba(ks[0], cfg, dtype)
    elif kind == MLSTM:
        p["mlstm"], s["mlstm"] = xlstm_mod.init_mlstm(ks[0], cfg, dtype)
    elif kind == SLSTM:
        p["slstm"], s["slstm"] = xlstm_mod.init_slstm(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if _has_ffn(cfg, kind):
        p["norm2"], s["norm2"] = init_rms_norm(cfg.d_model)
        if cfg.is_moe:
            p["moe"], s["moe"] = init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"], s["mlp"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p, s


# ---------------------------------------------------------------- block apply

def _block_window(cfg, kind, long_context):
    if kind == LOCAL_ATTN:
        return cfg.sliding_window
    if long_context:           # dense fallback: windowed global attention
        return cfg.long_context_window
    return None


def apply_block(params, x, kind, cfg, mode, positions, cache,
                long_context=False, cache_len=0, page_table=None,
                slots=None, attn_mask=None, layer=None):
    """Returns (y, new_cache, aux). ``layer``: a paged cache holds every
    layer's pool stacked and this block uses entry ``layer`` of it."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    window = _block_window(cfg, kind, long_context)
    if kind in _ATTN_KINDS:
        with jax.named_scope("attention"):
            if mode == "train":
                y, new_cache = attn_mod.causal_attention(
                    params["attn"], h, positions, cfg, window), None
            elif mode == "prefill":
                y, new_cache = attn_mod.prefill_attention(
                    params["attn"], h, positions, cfg, cache_len, window)
            elif page_table is not None:
                y, new_cache = attn_mod.paged_decode_attention(
                    params["attn"], h, cache, page_table, positions, cfg,
                    window, slots=slots, attn_mask=attn_mask, layer=layer)
            else:
                y, new_cache = attn_mod.decode_attention(
                    params["attn"], h, cache, positions, cfg, window,
                    slots=slots, attn_mask=attn_mask)
    elif kind == MAMBA:
        if mode == "decode":
            y, st = ssm_mod.mamba_decode(params["mamba"], h, cfg,
                                         cache["state"], cache["conv"])
        else:
            y, st = ssm_mod.mamba_forward(params["mamba"], h, cfg)
        new_cache = {"state": st[0], "conv": st[1]} if mode != "train" else None
    elif kind == MLSTM:
        if mode == "decode":
            y, st = xlstm_mod.mlstm_decode(params["mlstm"], h, cfg, cache["state"])
        else:
            y, st = xlstm_mod.mlstm_forward(params["mlstm"], h, cfg)
        new_cache = {"state": st} if mode != "train" else None
    elif kind == SLSTM:
        carry = cache["carry"] if mode == "decode" else None
        y, carry = xlstm_mod.slstm_forward(params["slstm"], h, cfg, carry)
        new_cache = {"carry": carry} if mode != "train" else None
    else:
        raise ValueError(kind)
    x = x + y
    if _has_ffn(cfg, kind):
        with jax.named_scope("mlp"):
            h = rms_norm(x, params["norm2"], cfg.norm_eps)
            if cfg.is_moe:
                y, aux = moe_ffn(params["moe"], h, cfg)
            else:
                h = _maybe_seq_shard_ffn(h)  # §Perf it.3: context-parallel FFN
                y = swiglu(params["mlp"], h)
        x = x + y
    return x, new_cache, aux


def _maybe_seq_shard_ffn(h):
    """Optimized profile, long sequences: shard the FFN input on sequence
    over the model axis. The FFN becomes (B, S/16, d) x TP weights with a
    (B, S/16, d) psum + regather — ~16x less activation collective volume
    than the replicated-sequence TP exchange (measured 2x805 MB/layer on
    phi4 prefill_32k)."""
    from ..sharding import context as shctx
    mesh = shctx.get_mesh()
    if mesh is None or not shctx.optimized():
        return h
    maxis = shctx.model_axis()
    S = h.shape[1]
    if S < 4096 or S % mesh.shape[maxis] != 0:
        return h
    daxes = shctx.data_axes()
    nB = 1
    for a in daxes:
        nB *= mesh.shape[a]
    b = daxes if h.shape[0] % nB == 0 else ()
    return shctx.maybe_constraint(h, b, maxis, None)


# ---------------------------------------------------------------- cache init

def _block_cache(cfg, kind, batch, max_len, dtype, long_context,
                 kv_quant=False):
    if kind in _ATTN_KINDS:
        window = _block_window(cfg, kind, long_context)
        size = min(max_len, window) if window else max_len
        hd = cfg.head_dim_
        kv_dtype = jnp.int8 if kv_quant else dtype
        k = jnp.zeros((batch, size, cfg.num_kv_heads, hd), kv_dtype)
        cache = {"k": k, "v": jnp.zeros_like(k),
                 "pos": jnp.full((batch, size), -1, jnp.int32)}
        if kv_quant:
            s = jnp.zeros((batch, size, cfg.num_kv_heads), jnp.float32)
            # distinct buffers: a donated cache may not alias two leaves
            cache.update(k_scale=s, v_scale=jnp.zeros_like(s))
        return cache
    if kind == MAMBA:
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == MLSTM:
        return xlstm_mod.init_mlstm_cache(cfg, batch)
    if kind == SLSTM:
        return xlstm_mod.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch, max_len, long_context=False, kv_quant=False):
    """Cache pytree: {"groups": tuple-per-sublayer stacked over n, "rem": ...}.

    ``kv_quant`` builds the int8 layout (repro.quant.kvcache): int8 k/v plus
    per-(slot, head) fp32 "k_scale"/"v_scale" leaves that the attention
    layers dispatch on."""
    g, n, rem = cfg.pattern_blocks()
    dtype = cfg.compute_dtype

    def stacked(kind, count):
        one = _block_cache(cfg, kind, batch, max_len, dtype, long_context,
                           kv_quant)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), one)

    cache = {"groups": tuple(stacked(kind, n) for kind in g) if n else (),
             "rem": tuple(_block_cache(cfg, kind, batch, max_len, dtype,
                                       long_context, kv_quant)
                          for kind in rem)}
    return cache


def init_paged_cache(cfg, num_pages, page_size, kv_quant=False):
    """Paged-pool cache pytree, same {"groups", "rem"} layout as init_cache.

    Per attention sublayer the pool is {"k": (P, page, Hkv, hd), "v": same,
    "page_pos": (P, page)} — no batch axis; rows of different lengths share
    the pool through a page table (serving.kv_pool). Physical page 0 is the
    reserved null page. ``kv_quant`` stores int8 k/v plus per-(page slot,
    head) "k_scale"/"v_scale" (P, page, Hkv). Only attention-only patterns
    are supported: recurrent state is O(1) per row and has nothing to page.
    """
    g, n, rem = cfg.pattern_blocks()
    dtype = cfg.compute_dtype

    def one(kind):
        if kind not in _ATTN_KINDS:
            raise ValueError(
                f"paged KV cache requires an attention-only pattern; got {kind}")
        k = jnp.zeros((num_pages, page_size, cfg.num_kv_heads, cfg.head_dim_),
                      jnp.int8 if kv_quant else dtype)
        cache = {"k": k, "v": jnp.zeros_like(k),
                 "page_pos": jnp.full((num_pages, page_size), -1, jnp.int32)}
        if kv_quant:
            s = jnp.zeros((num_pages, page_size, cfg.num_kv_heads), jnp.float32)
            cache.update(k_scale=s, v_scale=jnp.zeros_like(s))
        return cache

    def stacked(kind, count):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), one(kind))

    return {"groups": tuple(stacked(kind, n) for kind in g) if n else (),
            "rem": tuple(one(kind) for kind in rem)}


def paged_cache_specs(cache):
    """Logical sharding specs of a paged pool's leaves (``init_paged_cache``,
    arrays or shapes): K/V ``(..., Hkv, hd)`` and the int8 scales ``(...,
    Hkv)`` split on the KV-head axis as the heads' tensor-parallel axis
    ("tp", as ``wk``/``wv`` split their columns), slot positions replicated.
    The pool has no per-row sequence axis, so "cache_seq" never applies."""
    def spec(path, x):
        name = path[-1].key
        if name in ("k", "v"):
            return (None,) * (x.ndim - 2) + ("tp", None)
        if name in ("k_scale", "v_scale"):
            return (None,) * (x.ndim - 1) + ("tp",)
        return (None,) * x.ndim
    return jax.tree_util.tree_map_with_path(spec, cache)


# ---------------------------------------------------------------- model init

def init_params(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    g, n, rem = cfg.pattern_blocks()
    k_emb, k_head, k_groups, k_rem, k_shared = jax.random.split(key, 5)

    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = init_embedding(
        k_emb, cfg.vocab_size, cfg.d_model, dtype, cfg.num_codebooks)
    if not cfg.tie_embeddings:
        params["lm_head"], specs["lm_head"] = init_lm_head(
            k_head, cfg.d_model, cfg.vocab_size, dtype, cfg.num_codebooks)
    params["final_norm"], specs["final_norm"] = init_rms_norm(cfg.d_model)

    if n:
        def init_group(gkey):
            ks = jax.random.split(gkey, len(g))
            ps, ss = zip(*[init_block(ks[j], cfg, kind, dtype)
                           for j, kind in enumerate(g)])
            return tuple(ps), tuple(ss)
        gkeys = jax.random.split(k_groups, n)
        stacked = jax.vmap(lambda k: init_group(k)[0])(gkeys)
        params["groups"] = stacked
        specs["groups"] = jax.tree.map(
            lambda sp: (None,) + tuple(sp),
            init_group(gkeys[0])[1], is_leaf=lambda x: isinstance(x, tuple) and
            all(isinstance(e, (str, type(None))) for e in x))
    else:
        params["groups"], specs["groups"] = (), ()

    rkeys = jax.random.split(k_rem, max(len(rem), 1))
    rp = [init_block(rkeys[j], cfg, kind, dtype) for j, kind in enumerate(rem)]
    params["rem"] = tuple(p for p, _ in rp)
    specs["rem"] = tuple(s for _, s in rp)

    if SHARED_ATTN in g or SHARED_ATTN in rem:
        nsets = cfg.num_shared_attn_sets
        skeys = jax.random.split(k_shared, nsets)

        def init_shared(kk):
            ks = jax.random.split(kk, 2)
            p, _ = init_block(ks[0], cfg, SHARED_ATTN, dtype)
            return p
        params["shared_attn"] = jax.vmap(init_shared)(skeys)
        _, sspec0 = init_block(skeys[0], cfg, SHARED_ATTN, dtype)
        specs["shared_attn"] = jax.tree.map(
            lambda sp: (None,) + tuple(sp), sspec0,
            is_leaf=lambda x: isinstance(x, tuple))
    return params, specs


# ---------------------------------------------------------------- forward

def _select_shared(shared_params, idx, nsets):
    return jax.tree.map(lambda a: a[idx % nsets], shared_params)


def _run_pattern(params_list, kinds, x, cfg, mode, positions, caches,
                 shared_params, group_idx, long_context, cache_len,
                 page_table=None, slots=None, attn_mask=None, layer=None):
    """Apply one group's sublayers in order. caches: tuple aligned w/ kinds."""
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    for j, kind in enumerate(kinds):
        cache_j = caches[j] if caches else None
        if kind == SHARED_ATTN:
            bp = _select_shared(shared_params, group_idx, cfg.num_shared_attn_sets)
        else:
            bp = params_list[j]
        x, nc, aux = apply_block(bp, x, kind, cfg, mode, positions, cache_j,
                                 long_context, cache_len, page_table,
                                 slots, attn_mask, layer)
        new_caches.append(nc)
        aux_total = aux_total + aux
    return x, tuple(new_caches), aux_total


def backbone(params, tokens, cfg, mode="train", positions=None, cache=None,
             long_context=False, cache_len=0, inputs_embeds=None,
             page_table=None, slots=None, attn_mask=None):
    """tokens: (B, S) int32 (or (B, K, S) multi-codebook).

    Returns (hidden (B,S,D), new_cache or None, aux_loss).
    """
    g, n, rem = cfg.pattern_blocks()
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.compute_dtype)
    else:
        x = embed_tokens(params["embed"], tokens).astype(cfg.compute_dtype)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    shared_params = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)
    caches_out = {"groups": (), "rem": ()}

    if n:
        group_caches = cache["groups"] if cache is not None else None
        # paged pools ride the scan's carry whole, each layer updating its
        # own entry in place; scanned as xs -> ys they would be copied into
        # a fresh output stack, and a kernel reading them would need each
        # layer's pool sliced out into a buffer of its own
        paged = page_table is not None and group_caches is not None

        def body(carry, xs):
            h, aux_acc, pools = carry
            gp, gc, idx = xs
            h, ncs, aux = _run_pattern(gp, g, h, cfg, mode, positions,
                                       pools if paged else gc,
                                       shared_params, idx, long_context,
                                       cache_len, page_table, slots,
                                       attn_mask, idx if paged else None)
            if paged:
                return (h, aux_acc + aux, ncs), ()
            return (h, aux_acc + aux, pools), ncs

        body_fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
        xs = (params["groups"], () if paged else group_caches, jnp.arange(n))
        carry = (x, aux_total, group_caches if paged else ())
        (x, aux_total, pools), ys = jax.lax.scan(body_fn, carry, xs)
        caches_out["groups"] = pools if paged else ys

    if rem:
        rem_caches = cache["rem"] if cache is not None else [None] * len(rem)
        new_rem = []
        for j, kind in enumerate(rem):
            bp = (params["rem"][j] if kind != SHARED_ATTN
                  else _select_shared(shared_params, n, cfg.num_shared_attn_sets))
            x, nc, aux = apply_block(bp, x, kind, cfg, mode, positions,
                                     rem_caches[j], long_context, cache_len,
                                     page_table, slots, attn_mask)
            new_rem.append(nc)
            aux_total = aux_total + aux
        caches_out["rem"] = tuple(new_rem)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out_cache = caches_out if mode != "train" else None
    return x, out_cache, aux_total


@jax.named_scope("lm_head")
def logits_from_hidden(params, hidden, cfg):
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        w = jnp.swapaxes(w, -1, -2)
    return lm_head_logits(w, hidden, cfg.final_softcap)


def forward(params, tokens, cfg, **kw):
    """Full forward to logits (eval / decode-sized inputs)."""
    hidden, cache, aux = backbone(params, tokens, cfg, **kw)
    return logits_from_hidden(params, hidden, cfg), cache, aux
