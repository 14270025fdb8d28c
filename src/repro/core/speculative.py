"""Speculative decoding engine (Leviathan et al. 2023), batched and jit-able.

Round protocol (committed length per row = L; ``pending`` = last committed
token not yet inside either KV cache):

  draft phase : feed [pending, x1 .. x_gamma] one token at a time, sampling
                x_{i+1} from the draft distribution p_{i+1} as we go
                (gamma+1 feeds; the final feed keeps the draft cache complete
                on full acceptance — one extra small-model step per block,
                documented engineering deviation from the paper's cost model).
  verify      : target consumes the same gamma+1 tokens -> q_1 .. q_{gamma+1}.
                Attention-only models do this in ONE decode call (T=gamma+1,
                the latency win speculative decoding exists for) and rewind by
                masking cache positions; models with recurrent layers
                (mamba/xlstm/hybrid) verify token-at-a-time with per-step
                cache snapshots, and rewind by *selecting* the snapshot at the
                accepted prefix (DESIGN.md §4 state-checkpointing).
  accept      : x_i accepted w.p. min(1, q_i(x_i)/p_i(x_i)); on first
                rejection the replacement is drawn from norm(max(q - p, 0));
                on full acceptance the bonus token comes from q_{gamma+1}
                (realized by padding p_{gamma+1} = 0 so the residual is q).

Both models' sampling distributions use the same temperature/top-p transform
(the modified-rejection-sampling requirement); temperature 0 reduces to exact
greedy verification.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import DictKey, tree_map_with_path

from ..configs.base import ATTN, LOCAL_ATTN, SHARED_ATTN
from ..models.model import Model
from .metrics import SDStats
from .sampling import probs_from_logits, residual_sample, sample_from_probs


def attention_only(cfg) -> bool:
    g, _, rem = cfg.pattern_blocks()
    return all(k in (ATTN, LOCAL_ATTN, SHARED_ATTN) for k in tuple(g) + tuple(rem))


# ----------------------------------------------------------- cache utilities

def _leaf_batch_axis(path) -> int:
    for p in path:
        if isinstance(p, DictKey) and p.key == "groups":
            return 1
    return 0


def tree_where_rows(row_mask, a, b):
    """Per-batch-row select between two cache pytrees. row_mask: (B,) bool."""
    B = row_mask.shape[0]

    def f(path, x, y):
        ax = _leaf_batch_axis(path)
        shape = [1] * x.ndim
        shape[ax] = B
        return jnp.where(row_mask.reshape(shape), x, y)

    return tree_map_with_path(f, a, b)


def select_snapshot(snapshots, n_acc):
    """snapshots: list of gamma+1 cache pytrees; n_acc: (B,) index per row."""
    out = snapshots[0]
    for j in range(1, len(snapshots)):
        out = tree_where_rows(n_acc >= j, snapshots[j], out)
    return out


def _leaf_name(path):
    last = path[-1]
    return last.key if isinstance(last, DictKey) else None


def trim_attn_cache(cache, limit):
    """Invalidate attention-cache entries with position > limit (B,).

    Position leaves are identified *by name* ("pos" in the per-row cache) —
    never by dtype, so unrelated int32 leaves (conv state, page tables, …)
    cannot be corrupted by the rewind.
    """
    def f(path, leaf):
        if _leaf_name(path) == "pos":
            ax = _leaf_batch_axis(path)
            shape = [1] * leaf.ndim
            shape[ax] = limit.shape[0]
            lim = limit.reshape(shape)
            return jnp.where(leaf > lim, -1, leaf)
        return leaf
    return tree_map_with_path(f, cache)


def trim_paged_cache(cache, page_table, limit):
    """Paged-pool rewind: invalidate "page_pos" entries with position > the
    owning row's limit. page_table: (B, max_pages) physical ids (0 = null);
    limit: (B,). The per-page limit vector is built by scattering each row's
    limit onto its pages (null page 0 takes the min of all rows — harmless,
    it is never read). With prefix sharing a page may appear in several
    rows' tables; its limit is then the min of their limits, which is still
    >= every position the page holds (shared pages contain only full prompt
    pages, all below each sharer's committed length) — so a rewind
    structurally cannot touch refcount>1 pages."""
    pos_leaves = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(cache)[0]
                  if _leaf_name(path) == "page_pos"]
    if not pos_leaves:
        return cache
    P = pos_leaves[0].shape[-2]
    imax = jnp.iinfo(jnp.int32).max
    page_limit = jnp.full((P,), imax, jnp.int32)
    flat_pages = page_table.reshape(-1)
    flat_lim = jnp.repeat(limit.astype(jnp.int32), page_table.shape[1])
    page_limit = page_limit.at[flat_pages].min(flat_lim)

    def f(path, leaf):
        if _leaf_name(path) == "page_pos":
            # (P, page) or (n, P, page): pages on axis ndim-2
            shape = [1] * leaf.ndim
            shape[-2] = P
            return jnp.where(leaf > page_limit.reshape(shape), -1, leaf)
        return leaf
    return tree_map_with_path(f, cache)


# ----------------------------------------------------------------- the round

@dataclass(frozen=True)
class SDConfig:
    gamma: int = 3
    temperature: float = 1.0
    top_p: float = 1.0
    long_context: bool = False
    # int8 KV caches for both models (repro.quant.kvcache): prefill caches
    # are converted once, decode writes quantized entries directly. Rides in
    # the frozen config so jitted rounds cache per quant mode.
    kv_quant: bool = False
    # quality telemetry (repro.obs.quality): the commit phase additionally
    # writes ``state["qual"]`` — per-draft-depth empirical TVD
    # 0.5*sum|p - q|, target entropy, and accept indicators, all pure
    # functions of tensors the round already computes (no extra randomness,
    # no sampling change: tokens are bit-identical with the mode on). The
    # engine fetches the buffer with its existing per-round device_get.
    quality: bool = False


def init_quality_buffer(batch: int, depth: int):
    """Zeroed ``state["qual"]`` buffer so the round's input/output pytree
    structures match from the first round (one compilation, not two).
    ``depth`` is gamma for chain rounds, tree depth for tree rounds."""
    return {"tvd": jnp.zeros((batch, depth), jnp.float32),
            "ent": jnp.zeros((batch, depth), jnp.float32),
            "acc": jnp.zeros((batch, depth), bool),
            "drafted": jnp.zeros((batch, depth), bool)}


def quality_buffer(p_sel, q_sel, n_acc, drafted=None):
    """Per-depth quality accumulators from the round's own distributions.

    p_sel/q_sel: (K, B, V) draft/target distributions along the speculated
    chain (or accepted tree path); n_acc: (B,). ``drafted`` marks positions
    whose distributions are genuine drafts (chain: all K; tree: only depths
    at or before the stop — deeper path entries repeat the stop node).
    Everything here is a pure function of already-computed tensors: no keys
    are consumed, so temp-0 output tokens are identical with the mode on.
    """
    K, B = p_sel.shape[0], p_sel.shape[1]
    tvd = 0.5 * jnp.abs(p_sel - q_sel).sum(-1).T               # (B, K)
    ent = -jnp.where(q_sel > 0,
                     q_sel * jnp.log(jnp.maximum(q_sel, 1e-30)),
                     0.0).sum(-1).T                            # (B, K)
    acc = jnp.arange(K)[None] < n_acc[:, None]                 # (B, K)
    if drafted is None:
        drafted = jnp.ones((B, K), bool)
    return {"tvd": tvd.astype(jnp.float32), "ent": ent.astype(jnp.float32),
            "acc": acc, "drafted": drafted}


def masked_page_table(state):
    """The page-table view a round's decode calls must use: inactive rows are
    masked to the null page (0) so their cache writes land in trash. None
    when the state is unpaged."""
    page_table = state.get("page_table")
    if page_table is None:
        return None
    active = state.get("active")
    if active is None:
        return page_table
    return jnp.where(active[:, None], page_table, 0)


@jax.named_scope("draft")
def sd_draft_phase(draft, target: Model, sdc: SDConfig,
                   d_params, t_params, state, key):
    """Draft phase of a chain round: sample x_1..x_gamma and their draft
    distributions. Returns a jit-able pytree ``draft_out`` consumed by
    ``sd_verify_phase`` / ``sd_commit_phase``:

      x (g, B)              sampled draft tokens
      p_stack (g+1, B, V)   draft distributions (bonus slot zeroed)
      d_cache               drafter cache after the gamma+1 feeds (None for
                            head drafters — they keep no state)
      d_snaps               per-feed cache snapshots (recurrent drafters
                            only, for the rewind-by-selection), else None

    Each phase re-derives the same ``jax.random.split(key, gamma + 2)`` from
    the round key and consumes its fixed slice (``bench/lib/replay`` rebuilds
    the round's draws from that fan-out).
    """
    from ..draftheads.drafter import head_draft_chain, is_head_drafter
    head = is_head_drafter(draft)
    g = sdc.gamma
    lengths, pending = state["lengths"], state["pending"]
    d_cache = state.get("d_cache")
    B = pending.shape[0]
    keys = jax.random.split(key, g + 2)

    page_table = masked_page_table(state)
    dec_kw = {}
    if page_table is not None:
        if not attention_only(target.cfg) or \
                (not head and not attention_only(draft.cfg)):
            raise ValueError("paged sd_round requires attention-only models")
        dec_kw["page_table"] = page_table

    if head:
        # gamma head calls, zero drafter state
        x, p_stack = head_draft_chain(draft, d_params, t_params, target.cfg,
                                      sdc, state["h_feat"], pending,
                                      list(keys[:g]))
        return {"x": x, "p_stack": p_stack, "d_cache": None, "d_snaps": None}

    # gamma+1 single-token feeds
    d_recurrent = not attention_only(draft.cfg)
    xs = []          # sampled draft tokens x_1..x_gamma
    ps = []          # p_1 .. p_{gamma+1}
    # snapshot j (0-indexed) = cache after j+1 feeds, positions <= L+j;
    # the rewind target is positions <= L+n_acc -> snapshot index n_acc.
    d_snaps = [] if d_recurrent else None
    tok = pending
    for j in range(g + 1):
        pos = (lengths + j)[:, None]
        logits, d_cache = draft.decode_step(d_params, tok[:, None], pos,
                                            d_cache,
                                            long_context=sdc.long_context,
                                            **dec_kw)
        p = probs_from_logits(logits[:, 0], sdc.temperature, sdc.top_p)
        ps.append(p)
        if d_recurrent:
            d_snaps.append(d_cache)
        if j < g:
            tok = sample_from_probs(keys[j], p)
            xs.append(tok)
    x = jnp.stack(xs, 0) if g > 0 else jnp.zeros((0, B), jnp.int32)  # (g, B)
    p_stack = jnp.stack(ps, 0)                                   # (g+1, B, V)
    p_stack = p_stack.at[g].set(0.0)  # bonus slot: residual of 0 == q
    return {"x": x, "p_stack": p_stack, "d_cache": d_cache, "d_snaps": d_snaps}


@jax.named_scope("verify")
def sd_verify_phase(draft, target: Model, sdc: SDConfig,
                    t_params, state, draft_out):
    """Target verify: score the gamma+1 speculated tokens. Returns
    ``verify_out`` = {q_stack (g+1, B, V), t_cache, t_snaps, t_hid}."""
    from ..draftheads.drafter import is_head_drafter
    head = is_head_drafter(draft)
    g = sdc.gamma
    lengths, pending = state["lengths"], state["pending"]
    t_cache = state["t_cache"]
    x = draft_out["x"]
    dec_kw = {}
    page_table = masked_page_table(state)
    if page_table is not None:
        dec_kw["page_table"] = page_table

    feed = jnp.concatenate([pending[:, None], x.T], axis=1)           # (B, g+1)
    positions = lengths[:, None] + jnp.arange(g + 1)[None]
    t_recurrent = not attention_only(target.cfg)
    t_hid, t_snaps = None, None
    if t_recurrent:
        qs, t_snaps, hs = [], [], []
        for j in range(g + 1):
            out = target.decode_step(
                t_params, feed[:, j:j + 1], positions[:, j:j + 1], t_cache,
                long_context=sdc.long_context, return_hidden=head)
            logits, t_cache = out[0], out[1]
            qs.append(probs_from_logits(logits[:, 0], sdc.temperature, sdc.top_p))
            t_snaps.append(t_cache)
            if head:
                hs.append(out[2][:, 0])
        q_stack = jnp.stack(qs, 0)                                    # (g+1, B, V)
        if head:
            t_hid = jnp.stack(hs, 1)                                  # (B, g+1, D)
    else:
        out = target.decode_step(t_params, feed, positions, t_cache,
                                 long_context=sdc.long_context,
                                 return_hidden=head, **dec_kw)
        logits, t_cache = out[0], out[1]
        if head:
            t_hid = out[2]                                            # (B, g+1, D)
        q_stack = jnp.moveaxis(
            probs_from_logits(logits, sdc.temperature, sdc.top_p), 1, 0)
    return {"q_stack": q_stack, "t_cache": t_cache, "t_snaps": t_snaps,
            "t_hid": t_hid}


@jax.named_scope("commit")
def sd_commit_phase(draft, target: Model, sdc: SDConfig,
                    state, draft_out, verify_out, key):
    """Acceptance + residual sampling + token commit + cache rewind.
    Takes the same round ``key`` as the other phases (fixed split slices)
    and returns the round contract ``(new_state, n_acc)``."""
    from ..draftheads.drafter import is_head_drafter
    head = is_head_drafter(draft)
    g = sdc.gamma
    tokens, lengths, pending = state["tokens"], state["lengths"], state["pending"]
    active = state.get("active")
    page_table = state.get("page_table")
    x, p_stack = draft_out["x"], draft_out["p_stack"]
    d_cache, d_snaps = draft_out["d_cache"], draft_out["d_snaps"]
    q_stack, t_cache = verify_out["q_stack"], verify_out["t_cache"]
    t_snaps, t_hid = verify_out["t_snaps"], verify_out["t_hid"]
    B = pending.shape[0]
    keys = jax.random.split(key, g + 2)
    feed = jnp.concatenate([pending[:, None], x.T], axis=1)           # (B, g+1)

    # ---------------- acceptance -------------------------------------------
    if g > 0:
        bidx = jnp.arange(B)
        px = p_stack[jnp.arange(g)[:, None], bidx[None], x]           # (g, B)
        qx = q_stack[jnp.arange(g)[:, None], bidx[None], x]
        ratio = qx / jnp.maximum(px, 1e-20)
        u = jax.random.uniform(keys[g], (g, B))
        acc = (u < ratio).astype(jnp.int32)
        n_acc = jnp.cumprod(acc, axis=0).sum(0)                       # (B,)
    else:
        n_acc = jnp.zeros((B,), jnp.int32)

    bidx = jnp.arange(B)
    q_sel = q_stack[n_acc, bidx]                                      # (B, V)
    p_sel = p_stack[n_acc, bidx]
    new_pending = residual_sample(keys[g + 1], q_sel, p_sel)

    # ---------------- commit tokens ----------------------------------------
    vals = feed                                                       # (B, g+1)
    offs = jnp.arange(g + 1)[None]
    valid = offs <= n_acc[:, None]
    if active is not None:
        valid = valid & active[:, None]
    idx = jnp.where(valid, lengths[:, None] + offs, tokens.shape[1] - 1)
    tokens = tokens.at[bidx[:, None], idx].set(
        jnp.where(valid, vals, tokens[bidx[:, None], idx]))
    new_lengths = lengths + n_acc + 1
    if active is not None:
        new_lengths = jnp.where(active, new_lengths, lengths)
        new_pending = jnp.where(active, new_pending, pending)

    # ---------------- cache rewind ------------------------------------------
    limit = lengths + n_acc           # keep cache positions <= limit
    if page_table is not None:
        mpt = masked_page_table(state)
        if not head:
            d_cache = trim_paged_cache(d_cache, mpt, limit)
        t_cache = trim_paged_cache(t_cache, mpt, limit)
    else:
        if not head:
            if d_snaps is not None:    # recurrent drafter: rewind by selection
                d_cache = select_snapshot(d_snaps, n_acc)
                d_cache = trim_attn_cache(d_cache, limit)  # hybrids: attn too
            else:
                d_cache = trim_attn_cache(d_cache, limit)
        if t_snaps is not None:        # recurrent target
            t_cache = select_snapshot(t_snaps, n_acc)
            t_cache = trim_attn_cache(t_cache, limit)
        else:
            t_cache = trim_attn_cache(t_cache, limit)

    new_state = {"tokens": tokens, "lengths": new_lengths, "pending": new_pending,
                 "t_cache": t_cache}
    if sdc.quality:
        # per-draft-depth TVD/entropy/accept buffer — every chain position
        # is a genuine draft, so the drafted mask is all-ones
        new_state["qual"] = quality_buffer(p_stack[:g], q_stack[:g], n_acc)
    if head:
        # feature at the last committed position (L + n_acc): verify hidden
        # slot j sits at position L + j. Frozen rows keep their old feature.
        new_h = t_hid[bidx, n_acc]
        if active is not None:
            new_h = jnp.where(active[:, None], new_h, state["h_feat"])
        new_state["h_feat"] = new_h
    else:
        new_state["d_cache"] = d_cache
    if active is not None:
        new_state["active"] = active
    if page_table is not None:
        new_state["page_table"] = page_table
    return new_state, n_acc


def sd_round(draft, target: Model, sdc: SDConfig,
             d_params, t_params, state, key):
    """One speculative block. state: dict(tokens, lengths, pending, d_cache,
    t_cache). Returns (new_state, n_acc (B,)).

    ``draft`` is either a drafter ``Model`` or a ``draftheads.HeadDrafter``.
    With a head drafter the state carries no ``d_cache``; instead ``h_feat``
    (B, D) holds the target's final hidden state at the last committed
    position — drafting runs off it (``head_draft_chain``), the verify pass
    refreshes it (``return_hidden``), and there is no draft cache to rewind.

    Two optional state keys support continuous batching (serving.continuous):
      active (B,) bool     — rows with False are frozen: lengths/pending/token
                             commits are gated, and their page-table rows are
                             masked to the null page so cache writes land in
                             trash. Membership changes are pure data — the
                             jitted round stays compiled.
      page_table (B, Mp)   — routes attention KV through the shared paged
                             pool (models.attention.paged_decode_attention);
                             requires attention-only draft AND target.

    The round is the composition of three phase functions (draft / verify /
    commit), jitted as ONE computation; each phase runs under its own
    ``jax.named_scope``, so a device trace splits the round's time by phase
    with no fence between them.
    """
    draft_out = sd_draft_phase(draft, target, sdc, d_params, t_params,
                               state, key)
    verify_out = sd_verify_phase(draft, target, sdc, t_params, state,
                                 draft_out)
    return sd_commit_phase(draft, target, sdc, state, draft_out, verify_out,
                           key)


def tree_sd_round(draft: Model, target: Model, sdc: SDConfig, tree,
                  d_params, t_params, state, key):
    """Tree-structured speculative block (repro.spectree): verifies a whole
    token tree in one target pass and commits the longest accepted root
    path. Same state contract as ``sd_round``; ``tree`` is a
    ``spectree.TreeSpec``. Implemented in ``spectree.round`` (imported
    lazily — spectree depends on this module's cache utilities)."""
    from ..spectree.round import tree_round
    return tree_round(draft, target, sdc, tree, d_params, t_params, state, key)


# ----------------------------------------------------------------- drivers

def bind_round(draft, target: Model, sdc: SDConfig, tree=None):
    """The round as a ``functools.partial`` of its static arguments: the
    chain ``sd_round`` when ``tree`` is None, else ``tree_sd_round`` over
    that ``spectree.TreeSpec``. Jitted, a partial keeps the program name
    every caller's round shares (the decode steps are partials too)."""
    if tree is None:
        return partial(sd_round, draft, target, sdc)
    return partial(tree_sd_round, draft, target, sdc, tree)


@lru_cache(maxsize=64)
def _cached_round_donated(draft: Model, target: Model, sdc: SDConfig,
                          tree=None):
    """One jitted round per (drafter, target, sd cfg, tree shape or None),
    with the ``state`` argument donated to XLA. Call it with ``tree``
    positional everywhere, so the same shapes find one cache entry and one
    compiled program.

    Every state leaf (token buffer, KV caches / paged pools) is aliased
    input->output instead of double-buffered, so the round's cache commit
    writes in place — the state working set stays one copy instead of two.
    The round's output avals match its input avals leaf-for-leaf (the jaxpr
    auditor pins this, ``analysis.jaxpr_audit``), which is what makes every
    leaf aliasable; the auditor also statically verifies the lowering
    actually applied the aliases.

    Callers MUST NOT touch the input state after the call: the generate
    drivers rebind their loop variable, the continuous engine replaces
    ``self._state``. Anything that re-reads a round's input state jits
    ``bind_round`` itself.
    """
    return jax.jit(bind_round(draft, target, sdc, tree), donate_argnums=(2,))


@lru_cache(maxsize=64)
def _cached_decode(model: Model, long_context: bool,
                   return_hidden: bool = False):
    """Decode step with the cache (argument 3) donated: every caller rebinds
    its cache to the result. Undonated, each call allocates a second cache
    while the host runs ahead of the device — on a chip, chunked prefill of
    one prompt then holds several full paged pools at once. With
    ``return_hidden`` it also returns the final hidden states (draft-head
    prefill needs the feature)."""
    return jax.jit(partial(model.decode_step, long_context=long_context,
                           return_hidden=return_hidden), donate_argnums=(3,))


def _prefill_state(draft, target, d_params, t_params, prompt, max_total,
                   sdc, key):
    from ..draftheads.drafter import is_head_drafter
    B, S = prompt.shape
    head = is_head_drafter(draft)
    if head:
        lg_t, t_cache, h = target.prefill(t_params, prompt,
                                          cache_len=max_total,
                                          long_context=sdc.long_context,
                                          return_hidden=True)
    else:
        lg_t, t_cache = target.prefill(t_params, prompt, cache_len=max_total,
                                       long_context=sdc.long_context)
        _, d_cache = draft.prefill(d_params, prompt, cache_len=max_total,
                                   long_context=sdc.long_context)
    if sdc.kv_quant:
        from ..quant.kvcache import quantize_kv_cache
        t_cache = quantize_kv_cache(t_cache)
        if not head:
            d_cache = quantize_kv_cache(d_cache)
    q0 = probs_from_logits(lg_t[:, 0], sdc.temperature, sdc.top_p)
    pending = sample_from_probs(key, q0)
    buf = jnp.zeros((B, max_total + sdc.gamma + 2), jnp.int32)
    buf = buf.at[:, :S].set(prompt)
    state = {"tokens": buf, "lengths": jnp.full((B,), S, jnp.int32),
             "pending": pending, "t_cache": t_cache}
    if head:
        state["h_feat"] = h[:, -1]
    else:
        state["d_cache"] = d_cache
    return state


def speculative_generate(draft, target: Model, d_params, t_params,
                         prompt, max_new_tokens: int, sdc: SDConfig,
                         key=None) -> Tuple[jnp.ndarray, SDStats]:
    """Generate ``max_new_tokens`` per row with speculative decoding.

    ``draft`` may be a drafter ``Model`` (d_params = model params) or a
    ``draftheads.HeadDrafter`` (d_params = head params; self-speculative,
    no second model). Returns (tokens (B, S+max_new...), stats).
    Block-efficiency statistics count only rounds in which a row was still
    active.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    B, S = prompt.shape
    max_total = S + max_new_tokens + sdc.gamma + 2
    k0, key = jax.random.split(key)
    state = _prefill_state(draft, target, d_params, t_params, prompt,
                           max_total, sdc, k0)
    if sdc.quality:
        state["qual"] = init_quality_buffer(B, sdc.gamma)

    # Donated round: the loop rebinds ``state`` every iteration and never
    # re-reads the previous one, so XLA can commit caches in place.
    round_fn = _cached_round_donated(draft, target, sdc, None)
    stats = SDStats()
    target_len = S + max_new_tokens
    # Host mirror of per-row lengths: known exactly after prefill, then
    # refreshed from the same transfer that fetches n_acc — one device_get
    # per round instead of two, and stats update vectorized over rows.
    lengths_host = np.full((B,), S, np.int64)
    t0 = time.perf_counter()
    while True:
        active = lengths_host < target_len
        if not active.any():
            break
        key, kr = jax.random.split(key)
        state, n_acc = round_fn(d_params, t_params, state, kr)
        lengths_host, n_acc_host = (np.asarray(a) for a in
                                    jax.device_get((state["lengths"], n_acc)))
        stats.update_batch(n_acc_host[active] + 1)
    stats.wall_time_s = time.perf_counter() - t0
    return state["tokens"], stats


def autoregressive_generate(model: Model, params, prompt, max_new_tokens: int,
                            temperature: float = 1.0, top_p: float = 1.0,
                            key=None, long_context: bool = False):
    """Plain AR decoding baseline (one token per model call)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    B, S = prompt.shape
    max_total = S + max_new_tokens + 1
    lg, cache = model.prefill(params, prompt, cache_len=max_total,
                              long_context=long_context)
    step = _cached_decode(model, long_context, False)
    toks = [prompt]
    key, k = jax.random.split(key)
    cur = sample_from_probs(k, probs_from_logits(lg[:, 0], temperature, top_p))
    t0 = time.perf_counter()
    for i in range(max_new_tokens):
        toks.append(cur[:, None])
        if i == max_new_tokens - 1:
            break
        pos = jnp.full((B, 1), S + i, jnp.int32)
        lg, cache = step(params, cur[:, None], pos, cache)
        key, k = jax.random.split(key)
        cur = sample_from_probs(k, probs_from_logits(lg[:, 0], temperature, top_p))
    dt = time.perf_counter() - t0
    return jnp.concatenate(toks, axis=1), dt
