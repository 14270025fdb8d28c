"""Mixture-of-Experts FFN with top-k routing (granite-moe, grok-1).

Dispatch is *sort-based with per-shard capacity* (Switch/GShard-style token
dropping), run under ``shard_map`` over the data axes so the token buffers
stay local to each data shard — the TPU-native analogue of expert-parallel
all-to-all without materializing the (N, E, C) one-hot dispatch tensor.
Expert weights are tensor-parallel over ``model`` on the per-expert d_ff dim
(expert counts 40 / 8 do not divide the fixed 16-way model axis, so we TP
*within* experts; see DESIGN.md §6).

A dense-dispatch exact path (every expert on every token, gated combine) is
kept as the correctness oracle for tests and tiny smoke configs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import _normal
from ..sharding import context


def init_moe(key, cfg, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    params = {
        "router": _normal(kr, (d, E), 1.0 / math.sqrt(d), jnp.float32),
        "w_gate": _normal(kg, (E, d, f), 1.0 / math.sqrt(d), dtype),
        "w_up": _normal(ku, (E, d, f), 1.0 / math.sqrt(d), dtype),
        "w_down": _normal(kd, (E, f, d), 1.0 / math.sqrt(f), dtype),
    }
    # Expert weights: TP over the per-expert d_ff dim (expert counts need not
    # divide the model axis; d_ff always does) + ZeRO-3/fsdp over data on the
    # d_model dim — at grok-1 scale (618 GB of experts) TP-only storage would
    # be 38 GB/chip. The shard_map region all-gathers one layer's experts over
    # the data axes before use (per-layer transient, DESIGN.md §6).
    specs = {
        "router": (None, None),
        "w_gate": (None, "expert_fsdp", "tp"),
        "w_up": (None, "expert_fsdp", "tp"),
        "w_down": (None, "tp", "expert_fsdp"),
    }
    return params, specs


def _route(x2d, router_w, k):
    """x2d: (N, d) -> (gates (N,k), experts (N,k), aux_loss scalar)."""
    logits = jnp.einsum("nd,de->ne", x2d.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    E = router_w.shape[1]
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)      # (N, k, E)
    frac_routed = onehot.sum(1).mean(0)                          # (E,)
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(frac_routed * mean_prob)
    return gates, experts, aux


def _expert_ffn(w_gate, w_up, w_down, buf):
    """buf: (E, C, d) -> (E, C, d) through per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(buf.dtype))


def _moe_local(params, x2d, cfg):
    """Sort-based capacity-dropping MoE over local tokens. x2d: (N, d)."""
    N, d = x2d.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gates, experts, aux = _route(x2d, params["router"], k)
    C = int(math.ceil(N * k / E * cfg.moe_capacity_factor))  # repolint: ignore[RL001] static shape math over config floats, no tracers
    C = max(8, -(-C // 8) * 8)  # round up, keep lanes-friendly

    fe = experts.reshape(-1)                                    # (N*k,)
    fg = gates.reshape(-1)
    tok = jnp.arange(N * k, dtype=jnp.int32) // k
    order = jnp.argsort(fe, stable=True)
    fe_s, fg_s, tok_s = fe[order], fg[order], tok[order]
    start = jnp.searchsorted(fe_s, jnp.arange(E), side="left")  # (E,)
    pos = jnp.arange(N * k, dtype=jnp.int32) - start[fe_s]
    keep = pos < C
    slot = jnp.where(keep, fe_s * C + pos, E * C)               # dropped -> overflow row

    buf = jnp.zeros((E * C + 1, d), x2d.dtype).at[slot].add(x2d[tok_s])
    out = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                      buf[: E * C].reshape(E, C, d))
    out_flat = jnp.concatenate([out.reshape(E * C, d),
                                jnp.zeros((1, d), out.dtype)], axis=0)
    contrib = out_flat[slot] * (fg_s * keep).astype(out.dtype)[:, None]
    y = jnp.zeros((N, d), x2d.dtype).at[tok_s].add(contrib)
    return y, aux


def _moe_dense(params, x2d, cfg):
    """Exact dense-dispatch oracle: all experts on all tokens."""
    N, d = x2d.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gates, experts, aux = _route(x2d, params["router"], k)
    all_out = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                          jnp.broadcast_to(x2d, (E, N, d)))     # (E, N, d)
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], experts].add(gates)
    y = jnp.einsum("ne,end->nd", combine.astype(x2d.dtype), all_out)
    return y, aux


def moe_ffn(params, x, cfg, dense: bool = False):
    """x: (B, S, d) -> (y, aux_loss).

    With a mesh installed (repro.sharding.context), runs under shard_map:
    tokens stay local to each data shard (per-shard capacity), expert weights
    stay TP-sharded over the model axis on d_ff, and the w_down partial sums
    are combined with one psum over 'model' — the collective the roofline
    pass attributes to the MoE layer.
    """
    B, S, d = x.shape
    fn = _moe_dense if dense else _moe_local
    mesh = context.get_mesh()
    daxes = context.data_axes()
    maxis = context.model_axis()
    if mesh is None or not daxes:
        y, aux = fn(params, x.reshape(B * S, d), cfg)
        return y.reshape(B, S, d), aux
    # batch=1 decode (long_500k): batch cannot shard over data -> tokens are
    # replicated across data shards; run the region without a data split.
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    if B % dsize != 0:
        daxes = ()

    in_pspecs = {
        "router": P(),
        "w_gate": P(None, daxes, maxis),
        "w_up": P(None, daxes, maxis),
        "w_down": P(None, maxis, daxes),
    }

    gather_axes = context.data_axes()      # fsdp storage axes (always)

    # ---- SPerf it. (grok decode): weight-stationary decode path -----------
    # Baseline ZeRO-gathers ~1.8 GB of expert weights per layer per decode
    # step (measured 1.58 s collective-bound on grok decode_32k). For small
    # token counts it is ~200x cheaper to move ACTIVATIONS through the
    # (data x model)-sharded weights: gather the few tokens, compute partial
    # matmuls against the local (E, d/D, F/M) slices, and psum the partials.
    tokens_per_chip = B * S / max(dsize, 1)
    if context.optimized() and tokens_per_chip <= 64 and not dense:
        return _moe_weight_stationary(params, x, cfg, mesh, daxes,
                                      gather_axes, maxis)

    def local_fn(p, xl):
        Bl, Sl, _ = xl.shape
        # ZeRO-3 gather of this layer's expert shards over the data axes
        # (transient: one layer's experts live at a time).
        p = dict(p)
        for ax_name in gather_axes:
            p["w_gate"] = jax.lax.all_gather(p["w_gate"], ax_name, axis=1, tiled=True)
            p["w_up"] = jax.lax.all_gather(p["w_up"], ax_name, axis=1, tiled=True)
            p["w_down"] = jax.lax.all_gather(p["w_down"], ax_name, axis=2, tiled=True)
        y, aux = fn(p, xl.reshape(Bl * Sl, d), cfg)
        y = jax.lax.psum(y, maxis)            # w_down f-contraction partials
        if daxes:
            aux = jax.lax.pmean(aux, daxes)
        return y.reshape(Bl, Sl, d), aux

    in_pspecs["w_gate"] = P(None, gather_axes, maxis)
    in_pspecs["w_up"] = P(None, gather_axes, maxis)
    in_pspecs["w_down"] = P(None, maxis, gather_axes)
    batch_spec = P(daxes, None, None) if daxes else P(None, None, None)
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(in_pspecs, batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(params, x)
    return y, aux


def _combined_axis_index(axes):
    """Linear index over a tuple of mesh axes (row-major)."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _moe_weight_stationary(params, x, cfg, mesh, daxes, gather_axes, maxis):
    """Decode-optimized MoE: activations move, weights stay sharded.

    Weights local slices inside the region: w_gate/w_up (E, d/D, F/M),
    w_down (E, F/M, d/D). Tokens are gathered across data shards (tiny at
    decode), routed identically everywhere (replicated router), dispatched
    into an (E, C, d) buffer, then partial matmuls against the local slices
    with psum over data (d-contraction) and model (F-contraction).
    Per-layer collective volume ~ O(N*d + E*C*F/M) bytes instead of the
    baseline's O(expert_param_bytes)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    dsize = 1
    for a in gather_axes:
        dsize *= mesh.shape[a]
    d_shard = d // dsize

    def local_fn(p, xl):
        Bl, Sl, _ = xl.shape
        # 1) all tokens everywhere (cheap: decode-sized N)
        x_all = xl.reshape(Bl * Sl, d)
        for a in daxes:
            x_all = jax.lax.all_gather(x_all, a, axis=0, tiled=True)
        N = x_all.shape[0]
        # 2) identical routing on every chip
        gates, experts, aux = _route(x_all, p["router"], k)
        C = int(math.ceil(N * k / E * cfg.moe_capacity_factor))  # repolint: ignore[RL001] static shape math over config floats, no tracers
        C = max(8, -(-C // 8) * 8)
        fe = experts.reshape(-1)
        fg = gates.reshape(-1)
        tok = jnp.arange(N * k, dtype=jnp.int32) // k
        order = jnp.argsort(fe, stable=True)
        fe_s, fg_s, tok_s = fe[order], fg[order], tok[order]
        start = jnp.searchsorted(fe_s, jnp.arange(E), side="left")
        pos = jnp.arange(N * k, dtype=jnp.int32) - start[fe_s]
        keep = pos < C
        slot = jnp.where(keep, fe_s * C + pos, E * C)
        buf = jnp.zeros((E * C + 1, d), x_all.dtype).at[slot].add(x_all[tok_s])
        buf = buf[: E * C].reshape(E, C, d)
        # 3) slice the d dim to this chip's fsdp shard and do partial matmuls
        didx = _combined_axis_index(gather_axes)
        buf_d = jax.lax.dynamic_slice_in_dim(buf, didx * d_shard, d_shard, axis=2)
        g = jnp.einsum("ecd,edf->ecf", buf_d, p["w_gate"].astype(buf.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf_d, p["w_up"].astype(buf.dtype))
        g = jax.lax.psum(g, gather_axes)       # complete the d contraction
        u = jax.lax.psum(u, gather_axes)
        h = jax.nn.silu(g) * u                 # (E, C, F/M) local
        y_d = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(buf.dtype))
        y_d = jax.lax.psum(y_d, maxis)         # complete the F contraction
        # y_d: (E, C, d/D) — this chip's d-slice for every dispatched token
        out_flat = jnp.concatenate(
            [y_d.reshape(E * C, d_shard),
             jnp.zeros((1, d_shard), y_d.dtype)], axis=0)
        contrib = out_flat[slot] * (fg_s * keep).astype(y_d.dtype)[:, None]
        y_all = jnp.zeros((N, d_shard), x_all.dtype).at[tok_s].add(contrib)
        # 4) reassemble full d (weights were sharded over gather_axes) and
        #    take this chip's token rows (tokens were split over daxes)
        for a in reversed(gather_axes):
            y_all = jax.lax.all_gather(y_all, a, axis=1, tiled=True)
        if daxes:
            tidx = _combined_axis_index(daxes)
            nl = Bl * Sl
            y_loc = jax.lax.dynamic_slice_in_dim(y_all, tidx * nl, nl, axis=0)
        else:
            y_loc = y_all
        return y_loc.reshape(Bl, Sl, d), aux

    in_pspecs = {
        "router": P(),
        "w_gate": P(None, gather_axes, maxis),
        "w_up": P(None, gather_axes, maxis),
        "w_down": P(None, maxis, gather_axes),
    }
    batch_spec = P(daxes, None, None) if daxes else P(None, None, None)
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(in_pspecs, batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(params, x)
    return y, aux
