"""Tree-structured speculative decoding round (SpecInfer-style).

One round verifies a whole token *tree* in a single target decode call
instead of a single chain, raising expected accepted tokens per (memory
bound) target pass:

  draft phase : level-by-level expansion. The root is the round's pending
                token; at each level the draft scores all of the level's
                nodes in ONE decode call (siblings share RoPE position
                L+depth but occupy distinct cache slots L+node_index, with an
                ancestor attention mask), then samples ``branching[d]``
                children per node i.i.d. from the node's draft distribution.
  verify      : the target scores ALL N tree nodes in ONE decode call with
                the full ancestor mask -> q_u per node (the distribution the
                target would use *after* u's root path).
  accept      : recursive rejection sampling down the tree. At an accepted
                node u with children c_1..c_k (i.i.d. draws from p_u) the
                residual starts at q_u; child j is accepted with probability
                min(1, residual(t_j)/p_u(t_j)); on rejection the residual
                becomes norm(max(residual - p_u, 0)) and the next sibling is
                tried. Each stage is exact single-draft rejection sampling
                against the current residual, so the committed-token marginal
                equals target-only sampling (SpecInfer Thm; Leviathan Thm 1
                is the k=1 case). If no child survives, the next pending
                token is drawn from the final residual; at an accepted leaf
                it is drawn from q_leaf (the bonus token). Temperature 0
                makes every distribution one-hot and the scheme reduces to
                the longest greedy path.
  commit      : only the accepted root path enters the KV caches. Path
                nodes' K/V are gathered from their tree slots and rewritten
                at canonical contiguous positions L..L+n_acc; every other
                tree slot is invalidated (pos = -1), so rejected siblings can
                never leak into later attention. Works for both the dense
                ring cache and the shared paged pool (storage position ->
                page via the row's page table; masked-out rows write to the
                null page, page 0).

State layout and the ``active``/``page_table`` continuous-batching keys are
identical to ``core.speculative.sd_round``; the round returns the same
``(new_state, n_acc)`` contract so the serving engine can swap rounds.
"""
from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.metrics import SDStats
from ..core.sampling import probs_from_logits, sample_from_probs
from ..core.speculative import (SDConfig, _leaf_batch_axis, _leaf_name,
                                _prefill_state, attention_only,
                                init_quality_buffer, masked_page_table,
                                quality_buffer)
from ..kernels.tree_attention import kv_window
from ..models.model import Model
from .tree import TreeSpec, tree_attn_mask


def _cache_view_width(cache, page_table) -> int:
    """Slot count of the attention view the masks must align with."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    if page_table is not None:
        pages = [lf.shape[-1] for p, lf in leaves if _leaf_name(p) == "page_pos"]
        return page_table.shape[1] * pages[0]
    widths = {lf.shape[-1] for p, lf in leaves if _leaf_name(p) == "pos"}
    if len(widths) != 1:
        raise ValueError(
            f"tree decoding needs one uniform attention-cache width, got "
            f"{sorted(widths)} (mixed sliding-window caches are unsupported)")
    return widths.pop()


# ------------------------------------------------------------- path commit

def commit_tree_path(cache, lengths, path_nodes, n_acc, num_nodes):
    """Dense-cache root-path commit + tree-region invalidation.

    path_nodes: (B, depth+1) flattened node index of the accepted path at
    each depth (entries beyond n_acc repeat the last node — they are written
    with pos -1 so they stay invisible). Node i's K/V sits at slot
    ``(lengths + i) % Smax``; the accepted depth-d node is rewritten to the
    canonical slot ``(lengths + d) % Smax`` with position ``lengths + d``.
    """
    B, Dp1 = path_nodes.shape
    offs = jnp.arange(Dp1)
    bidx = jnp.arange(B)[:, None]

    def f(path, leaf):
        name = _leaf_name(path)
        # int8-KV caches carry per-slot "k_scale"/"v_scale" leaves that must
        # ride along with their k/v entries (repro.quant.kvcache)
        if name not in ("k", "v", "pos", "k_scale", "v_scale"):
            return leaf
        ax = _leaf_batch_axis(path)
        S = leaf.shape[ax + 1]
        src = (lengths[:, None] + path_nodes) % S
        dst = (lengths[:, None] + offs[None]) % S
        tree_slots = (lengths[:, None] + jnp.arange(num_nodes)[None]) % S
        if name == "pos":
            canon = jnp.where(offs[None] <= n_acc[:, None],
                              lengths[:, None] + offs[None], -1).astype(jnp.int32)
            if ax == 0:
                return leaf.at[bidx, tree_slots].set(-1).at[bidx, dst].set(canon)
            return leaf.at[:, bidx, tree_slots].set(-1).at[:, bidx, dst].set(canon)
        if ax == 0:
            return leaf.at[bidx, dst].set(leaf[bidx, src])
        return leaf.at[:, bidx, dst].set(leaf[:, bidx, src])

    return jax.tree_util.tree_map_with_path(f, cache)


def commit_tree_path_paged(cache, page_table, lengths, path_nodes, n_acc,
                           num_nodes):
    """Paged-pool root-path commit (``page_pos`` keyed, null-page safe).

    Rows whose page-table row is masked to the null page route every gather
    and scatter to page 0, whose contents are never read — so inactive rows
    are no-ops, same convention as ``paged_decode_attention``.

    Every gather/scatter here addresses storage positions >= the row's
    committed length (the node buffer lives at slots L .. L+num_nodes-1),
    which is what keeps prefix-shared pages (serving.prefix_cache) safe:
    shared pages hold only positions below every sharer's committed length,
    so the commit and the rejected-slot invalidation never reach them.
    """
    pages = [lf.shape[-1] for p, lf
             in jax.tree_util.tree_flatten_with_path(cache)[0]
             if _leaf_name(p) == "page_pos"]
    page = pages[0]
    B, Dp1 = path_nodes.shape
    offs = jnp.arange(Dp1)
    max_pages = page_table.shape[1]

    def phys_off(storage):                       # (B, X) absolute positions
        pidx = jnp.clip(storage // page, 0, max_pages - 1)
        return (jnp.take_along_axis(page_table, pidx, axis=1),
                (storage % page).astype(jnp.int32))

    src_p, src_o = phys_off(lengths[:, None] + path_nodes)
    dst_p, dst_o = phys_off(lengths[:, None] + offs[None])
    tree_p, tree_o = phys_off(lengths[:, None] + jnp.arange(num_nodes)[None])
    canon = jnp.where(offs[None] <= n_acc[:, None],
                      lengths[:, None] + offs[None], -1).astype(jnp.int32)

    def f(path, leaf):
        name = _leaf_name(path)
        stacked = _leaf_batch_axis(path) == 1    # (n, P, page, ...) groups
        if name == "page_pos":
            if stacked:
                return (leaf.at[:, tree_p, tree_o].set(-1)
                            .at[:, dst_p, dst_o].set(canon))
            return leaf.at[tree_p, tree_o].set(-1).at[dst_p, dst_o].set(canon)
        if name in ("k", "v", "k_scale", "v_scale"):
            if stacked:
                return leaf.at[:, dst_p, dst_o].set(leaf[:, src_p, src_o])
            return leaf.at[dst_p, dst_o].set(leaf[src_p, src_o])
        return leaf

    return jax.tree_util.tree_map_with_path(f, cache)


# ------------------------------------------------------------------ round

@jax.named_scope("draft")
def tree_draft_phase(draft, target: Model, sdc: SDConfig, spec: TreeSpec,
                     d_params, t_params, state, key):
    """Level-by-level tree expansion: sample every node's token and keep its
    draft distribution. Returns ``draft_out`` = {node_tok (N, B),
    p_node (N, B, V), d_cache (None for head drafters)}.

    Each phase re-derives the identical ``jax.random.split(key, n_keys)``
    and consumes its fixed slice (draft: the first ``depth`` keys, commit:
    the rest), so each phase's draws are fixed by the round key alone."""
    from ..draftheads.drafter import head_draft_tree, is_head_drafter
    head = is_head_drafter(draft)
    if not attention_only(target.cfg) or \
            (not head and not attention_only(draft.cfg)):
        raise ValueError("tree speculative decoding requires attention-only "
                         "draft and target (per-node cache slots)")
    lengths, pending = state["lengths"], state["pending"]
    d_cache = state.get("d_cache")
    B = pending.shape[0]
    N, D = spec.num_nodes, spec.depth
    starts = spec.level_starts

    page_table = masked_page_table(state)
    dec_kw = {} if page_table is None else {"page_table": page_table}

    n_keys = 2 * D + sum(spec.branching) + 1
    keys = iter(jax.random.split(key, n_keys))

    if head:
        level_keys = [next(keys) for _ in range(D)]
        node_tok, p_node = head_draft_tree(
            draft, d_params, t_params, target.cfg, sdc, spec,
            state["h_feat"], pending, level_keys)
        return {"node_tok": node_tok, "p_node": p_node, "d_cache": None}

    d_width = _cache_view_width(d_cache, dec_kw.get("page_table"))
    level_toks = [pending[:, None]]          # level d -> (B, n_d) tokens
    ps = []                                  # per level (n_d, B, V)
    for d in range(D + 1):
        s, e = starts[d], starts[d + 1]
        nl = e - s
        toks = level_toks[d]
        rope = jnp.broadcast_to((lengths + d)[:, None], (B, nl))
        slot_pos = lengths[:, None] + jnp.arange(s, e)[None]
        amask = tree_attn_mask(spec, s, e, lengths, d_width)
        logits, d_cache = draft.decode_step(
            d_params, toks, rope, d_cache, long_context=sdc.long_context,
            slots=slot_pos, attn_mask=amask, **dec_kw)
        p = probs_from_logits(logits, sdc.temperature, sdc.top_p)  # (B,nl,V)
        ps.append(jnp.moveaxis(p, 0, 1))
        if d < D:
            k_d = spec.branching[d]
            V = p.shape[-1]
            children = sample_from_probs(
                next(keys),
                jnp.broadcast_to(p[:, :, None, :], (B, nl, k_d, V)))
            level_toks.append(children.reshape(B, nl * k_d))
    p_node = jnp.concatenate(ps, 0)                           # (N, B, V)
    node_tok = jnp.concatenate(
        [jnp.moveaxis(t, 0, 1) for t in level_toks], 0)       # (N, B)
    return {"node_tok": node_tok, "p_node": p_node, "d_cache": d_cache}


@jax.named_scope("verify")
def tree_verify_phase(draft, target: Model, sdc: SDConfig, spec: TreeSpec,
                      t_params, state, draft_out):
    """Target verify: ONE decode over all N tree nodes with the ancestor
    mask. Returns ``verify_out`` = {q_node (N, B, V), t_cache, t_hid}."""
    from ..draftheads.drafter import is_head_drafter
    head = is_head_drafter(draft)
    lengths = state["lengths"]
    t_cache = state["t_cache"]
    node_tok = draft_out["node_tok"]
    N = spec.num_nodes
    page_table = masked_page_table(state)
    dec_kw = {} if page_table is None else {"page_table": page_table}

    t_width = _cache_view_width(t_cache, dec_kw.get("page_table"))
    feed = node_tok.T                                             # (B, N)
    rope = lengths[:, None] + jnp.asarray(spec.depths())[None]
    slot_pos = lengths[:, None] + jnp.arange(N)[None]
    amask = tree_attn_mask(spec, 0, N, lengths, t_width)
    out = target.decode_step(
        t_params, feed, rope, t_cache, long_context=sdc.long_context,
        slots=slot_pos, attn_mask=amask, return_hidden=head, **dec_kw)
    logits, t_cache = out[0], out[1]
    t_hid = out[2] if head else None                              # (B, N, D)
    q_node = jnp.moveaxis(
        probs_from_logits(logits, sdc.temperature, sdc.top_p), 1, 0)  # (N,B,V)
    return {"q_node": q_node, "t_cache": t_cache, "t_hid": t_hid}


@jax.named_scope("commit")
def tree_commit_phase(draft, target: Model, sdc: SDConfig, spec: TreeSpec,
                      state, draft_out, verify_out, key):
    """Recursive-rejection acceptance, token commit, and root-path cache
    commit. Takes the same round ``key`` (consumes the key slice after the
    draft phase's) and returns the round contract ``(new_state, n_acc)``."""
    from ..draftheads.drafter import is_head_drafter
    head = is_head_drafter(draft)
    tokens, lengths, pending = state["tokens"], state["lengths"], state["pending"]
    active = state.get("active")
    page_table = state.get("page_table")
    node_tok, p_node = draft_out["node_tok"], draft_out["p_node"]
    d_cache = draft_out["d_cache"]
    q_node, t_cache = verify_out["q_node"], verify_out["t_cache"]
    t_hid = verify_out["t_hid"]
    B = pending.shape[0]
    N, D = spec.num_nodes, spec.depth

    n_keys = 2 * D + sum(spec.branching) + 1
    all_keys = jax.random.split(key, n_keys)
    keys = iter(all_keys[D:])        # draft phase consumed the first D

    # ---------------- multi-path acceptance ---------------------------------
    children_tab = jnp.asarray(spec.children())                   # (N, kmax)
    bidx = jnp.arange(B)
    cur = jnp.zeros((B,), jnp.int32)
    n_acc = jnp.zeros((B,), jnp.int32)
    alive = jnp.ones((B,), bool)
    new_pending = jnp.zeros((B,), jnp.int32)
    path = [cur]
    for d in range(D):
        res = q_node[cur, bidx]                                   # (B, V)
        p_cur = p_node[cur, bidx]
        child_base = children_tab[cur]                            # (B, kmax)
        accepted = jnp.zeros((B,), bool)
        next_cur = cur
        for j in range(spec.branching[d]):
            cidx = child_base[:, j]
            t = node_tok[cidx, bidx]
            ratio = res[bidx, t] / jnp.maximum(p_cur[bidx, t], 1e-20)
            u = jax.random.uniform(next(keys), (B,))
            acc_j = alive & (~accepted) & (u < ratio)
            next_cur = jnp.where(acc_j, cidx, next_cur)
            accepted = accepted | acc_j
            # rows still rejecting: advance the residual past this sibling
            rej = alive & (~accepted)
            r = jnp.maximum(res - p_cur, 0.0)
            mass = r.sum(-1, keepdims=True)
            r = jnp.where(mass > 1e-9, r / jnp.maximum(mass, 1e-30), res)
            res = jnp.where(rej[:, None], r, res)
        stop = alive & (~accepted)
        tok_stop = sample_from_probs(next(keys), res)
        new_pending = jnp.where(stop, tok_stop, new_pending)
        alive = alive & accepted
        n_acc = n_acc + accepted.astype(jnp.int32)
        cur = next_cur
        path.append(cur)
    tok_bonus = sample_from_probs(next(keys), q_node[cur, bidx])
    new_pending = jnp.where(alive, tok_bonus, new_pending)
    path_nodes = jnp.stack(path, 1)                               # (B, D+1)

    # ---------------- commit tokens ----------------------------------------
    vals = node_tok[path_nodes, bidx[:, None]]                    # (B, D+1)
    offs = jnp.arange(D + 1)[None]
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

    # ---------------- cache path-commit ------------------------------------
    if page_table is not None:
        mpt = masked_page_table(state)
        if not head:
            d_cache = commit_tree_path_paged(d_cache, mpt,
                                             lengths, path_nodes, n_acc, N)
        t_cache = commit_tree_path_paged(t_cache, mpt,
                                         lengths, path_nodes, n_acc, N)
    else:
        if not head:
            d_cache = commit_tree_path(d_cache, lengths, path_nodes, n_acc, N)
        t_cache = commit_tree_path(t_cache, lengths, path_nodes, n_acc, N)

    new_state = {"tokens": tokens, "lengths": new_lengths,
                 "pending": new_pending, "t_cache": t_cache}
    if sdc.quality:
        # quality buffer along the accepted path: depth step d accepted the
        # child of path node d against (p, q) at that node. Path entries
        # past the stop repeat the stop node, so only depths <= n_acc are
        # genuine drafts — the drafted mask excludes the repeats.
        pn = path_nodes[:, :D]                                # (B, D)
        p_path = jnp.moveaxis(p_node[pn, bidx[:, None]], 1, 0)  # (D, B, V)
        q_path = jnp.moveaxis(q_node[pn, bidx[:, None]], 1, 0)
        drafted = jnp.arange(D)[None] <= n_acc[:, None]
        new_state["qual"] = quality_buffer(p_path, q_path, n_acc, drafted)
    if head:
        # feature at the deepest accepted node (depth n_acc, position
        # L + n_acc — the last committed position). The ancestor mask makes a
        # node's hidden state identical to a chain forward over its root
        # path, so this is exactly the feature the next round needs.
        new_h = t_hid[bidx, cur]
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


def tree_round(draft, target: Model, sdc: SDConfig, spec: TreeSpec,
               d_params, t_params, state, key):
    """One tree-speculative block. Same state contract as ``sd_round``;
    returns (new_state, n_acc (B,)) with n_acc = accepted draft tokens
    (committed tokens this round = n_acc + 1, plus the new pending).

    ``draft`` may be a drafter ``Model`` or a ``draftheads.HeadDrafter``:
    head drafting expands the tree from the target's last hidden state
    (state key ``h_feat``) with no draft cache — only the target cache takes
    the per-node slot writes and the root-path commit.

    Composed from three phase functions (draft expansion / verify /
    accept+commit) jitted as ONE computation; each runs under its own
    ``jax.named_scope``, which is how a device trace splits the round."""
    draft_out = tree_draft_phase(draft, target, sdc, spec, d_params,
                                 t_params, state, key)
    verify_out = tree_verify_phase(draft, target, sdc, spec, t_params,
                                   state, draft_out)
    return tree_commit_phase(draft, target, sdc, spec, state, draft_out,
                             verify_out, key)


# ----------------------------------------------------------------- driver

def tree_speculative_generate(draft, target: Model, d_params, t_params,
                              prompt, max_new_tokens: int, sdc: SDConfig,
                              spec: TreeSpec, key=None
                              ) -> Tuple[jnp.ndarray, SDStats]:
    """Generate with tree speculation; mirrors ``speculative_generate``."""
    from ..core.speculative import _cached_round_donated
    key = key if key is not None else jax.random.PRNGKey(0)
    B, S = prompt.shape
    # the tree-attention kernel needs a KV width it can tile
    max_total = kv_window(S + max_new_tokens + spec.num_nodes + 2)
    k0, key = jax.random.split(key)
    state = _prefill_state(draft, target, d_params, t_params, prompt,
                           max_total, sdc, k0)
    if sdc.quality:
        state["qual"] = init_quality_buffer(B, spec.depth)
    round_fn = _cached_round_donated(draft, target, sdc, spec)
    stats = SDStats()
    target_len = S + max_new_tokens
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
