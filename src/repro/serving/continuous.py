"""Continuous-batching speculative serving engine.

Unlike the static ``ServingEngine`` (one batch = one generation, grouped by
identical shapes), this engine keeps a fixed set of ``max_batch`` decode
slots running one shared jitted ``sd_round`` and changes *membership* between
rounds: new requests join as soon as a slot and KV pages free up, finished
rows retire immediately, and prompt prefill is fed through the paged decode
path in fixed-size chunks interleaved with decode rounds so a long prompt
never stalls ongoing generation for more than one chunk.

All shapes the jitted code sees are fixed at engine construction (slot count,
token-buffer width, page-table width, pool sizes); membership changes are
pure data (the ``active`` mask and page-table rows), so the round compiles
once. KV memory is a shared paged pool (serving.kv_pool): admission reserves
a request's worst case up front, which is what bounds the queue instead of
bounding concurrency by the longest request, and is why mixed-length traffic
batches instead of degenerating to batch size 1.

API: ``submit()`` (callbacks optional) / ``step()`` / ``stream()`` /
``serve()``; per-request ``RequestStats`` (TTFT/TPOT/tau) and engine-level
``ServingTelemetry`` (queue depth, active rows, free pages per step).

With ``tree=TreeSpec(...)`` the decode round is the tree-speculative one
(repro.spectree): per-row slack grows to the tree's node count (the whole
node buffer is written before the accepted root path is committed back and
rejected node slots are invalidated), and up to depth+1 tokens commit per
round instead of gamma+1.

With ``prefix_cache=True`` a radix cache (serving.prefix_cache) maps each
admitted request's longest cached prompt prefix read-only into its page
table: the scheduler's prefix probe stamps the hit before the capacity
check (a hit needs fewer fresh pages), chunked prefill resumes at the hit
boundary, an admission-time COW copies the tail shared page when the
resumed prefill would write into it, retirement invalidates only pages
whose refcount actually reached zero, and under pool pressure admission
evicts LRU radix leaves. At temperature 0 the output stream is token-
identical to the non-shared engine — the shared pages hold bit-identical
K/V to what the request's own prefill would have produced.
"""
from __future__ import annotations

import gc
import time
import weakref
from dataclasses import dataclass, field, replace
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.metrics import RequestStats, ServingTelemetry
from ..core.sampling import probs_from_logits, sample_from_probs
from ..core.speculative import (SDConfig, _cached_decode,
                                _cached_round_donated, attention_only,
                                bind_round, init_quality_buffer,
                                trim_paged_cache)
from ..draftheads import HeadDrafter
from ..kernels.tree_attention import kv_window
from ..models.model import Model
from ..obs import NULL_TRACER, FlightRecorder, QualityStats, SLOTracker
from ..sharding import context as shctx
from ..spectree.tree import TreeSpec
from .engine import Request, Result
from .kv_pool import (PagedKVPool, ceil_div, copy_pages, invalidate_pages,
                      state_shardings)
from .prefix_cache import PrefixCache
from .scheduler import Scheduler, ServeRequest


@lru_cache(maxsize=32)
def _cached_window_gather(span: int):
    # module-level (not per-engine) so a fresh engine with the same span
    # reuses the compiled program — the recompile sentinel pins this
    def _window_gather(toks, base):
        return jnp.take_along_axis(
            toks, base[:, None] + jnp.arange(span, dtype=base.dtype)[None],
            axis=1)
    return jax.jit(_window_gather)


def _trace_gc(engine):
    """Record every Python garbage collection as a ``gc`` span on the
    engine's tracer (generation; objects collected where the span keeps
    args) for as long as the engine lives. The hook holds the engine weakly
    and is removed when the engine is collected."""
    ref = weakref.ref(engine)
    open_spans = []

    def hook(phase, info):
        eng = ref()
        if eng is None:
            return
        if phase == "start":
            span = eng._tr.span("gc", generation=info["generation"])
            span.__enter__()
            open_spans.append(span)
        elif open_spans:
            span = open_spans.pop()
            args = getattr(span, "args", None)
            if args is not None:
                args["collected"] = info["collected"]
            span.__exit__(None, None, None)

    gc.callbacks.append(hook)
    weakref.finalize(engine, gc.callbacks.remove, hook)


def _init_state(target, draft, batch, buf, max_pages, num_pages, page_size,
                kv_quant, qdepth):
    """The engine's state as made: token buffer, lengths, pending tokens,
    active mask, page table and the paged pools, empty. Without a
    ``draft`` model (draft heads) there is no drafter pool, and ``h_feat``
    carries the target feature per slot; ``qdepth`` seeds the quality
    buffers so the round's input pytree matches its output from round 1
    (one compilation, not two)."""
    state = {
        "tokens": jnp.zeros((batch, buf), jnp.int32),
        "lengths": jnp.zeros((batch,), jnp.int32),
        "pending": jnp.zeros((batch,), jnp.int32),
        "active": jnp.zeros((batch,), bool),
        "page_table": jnp.zeros((batch, max_pages), jnp.int32),
        "t_cache": target.init_paged_cache(num_pages, page_size,
                                           kv_quant=kv_quant),
    }
    if draft is None:
        state["h_feat"] = jnp.zeros((batch, target.cfg.d_model),
                                    target.cfg.compute_dtype)
    else:
        state["d_cache"] = draft.init_paged_cache(num_pages, page_size,
                                                  kv_quant=kv_quant)
    if qdepth is not None:
        state["qual"] = init_quality_buffer(batch, qdepth)
    return state


def _jit_keeping(step, at, sharding, donate):
    """``step`` jitted with argument ``donate`` donated and its output
    ``at`` constrained to ``sharding``, the layout that argument comes in,
    so what was donated aliases what comes out."""
    def kept(*args, **kw):
        out = list(step(*args, **kw))
        out[at] = jax.lax.with_sharding_constraint(out[at], sharding)
        return tuple(out)
    return jax.jit(kept, donate_argnums=(donate,))


@lru_cache(maxsize=8)
def _cached_mesh_programs(mesh, drafter, sd, tree, return_hidden, *shape):
    """Across chips, the engine's layout over ``mesh`` and the programs
    that keep it: (jitted ``_init_state(*shape)`` that makes every leaf
    straight into its sharding, those shardings, the donated round, the
    drafter's and the target's donated prefill steps). The round returns
    the state, a prefill step its pool, in that layout. Module-level, so a
    fresh engine of the same shapes compiles nothing."""
    target, draft = shape[:2]
    make = partial(_init_state, *shape)
    layout = state_shardings(jax.eval_shape(make), mesh)
    lc = sd.long_context
    d_step = (None if draft is None else _jit_keeping(
        partial(draft.decode_step, long_context=lc), 1, layout["d_cache"], 3))
    t_step = _jit_keeping(partial(target.decode_step, long_context=lc,
                                  return_hidden=return_hidden),
                          1, layout["t_cache"], 3)
    return (jax.jit(make, out_shardings=layout), layout,
            _jit_keeping(bind_round(drafter, target, sd, tree), 0, layout, 2),
            d_step, t_step)


@dataclass
class _Slot:
    state: str = "free"                # free | prefill | decode
    req: Optional[ServeRequest] = None
    stats: Optional[RequestStats] = None
    prompt_len: int = 0
    target_len: int = 0                # prompt_len + max_new_tokens
    prefill_pos: int = 0               # prompt tokens fed so far
    emitted: int = 0                   # generated tokens already streamed
    admit_seq: int = 0


@dataclass
class ContinuousEngine:
    target: Model
    target_params: object
    draft: Model = None
    draft_params: object = None
    # self-speculative alternative to a separate drafter (repro.draftheads):
    # drafting runs off the target's hidden states, so the engine allocates
    # NO draft KV pool and prefill feeds only the target.
    draft_heads: Optional[HeadDrafter] = None
    draft_head_params: object = None
    sd: SDConfig = field(default_factory=SDConfig)
    tree: Optional[TreeSpec] = None    # tree-speculative rounds (spectree)
    max_batch: int = 8                 # concurrent decode slots
    max_seq_len: int = 256             # per-request prompt + max_new cap
    page_size: int = 16
    num_pages: Optional[int] = None    # default: worst case for max_batch rows
    prefill_chunk: int = 32
    policy: str = "fcfs"
    aging_s: Optional[float] = None    # priority aging (scheduler), seconds
    kv_quant: bool = False             # int8 KV pools (repro.quant.kvcache)
    # prefix sharing (serving.prefix_cache): radix cache over the paged pool
    # with per-page refcounts + COW — shared prompt prefixes prefill once and
    # are mapped read-only into every matching request's page table.
    prefix_cache: bool = False
    # observability (repro.obs): all opt-in, all off by default.
    # tracer — span tracer; per-request lifecycle tracks + engine-thread
    #   spans, exported as Chrome/Perfetto trace-event JSON (tracer.write).
    # registry — metrics registry; telemetry dataclasses emit into it live.
    # metrics_out — JSONL path; a registry snapshot is appended every
    #   ``metrics_every`` steps and once at drain.
    tracer: Optional[object] = None
    registry: Optional[object] = None
    metrics_out: Optional[str] = None
    metrics_every: int = 50
    # quality — speculation-quality telemetry (repro.obs.quality): the jitted
    #   round leaves per-depth TVD/entropy/accept buffers in the round state,
    #   fetched with the SAME per-round device_get as the token windows (no
    #   extra host syncs, temp-0 token-identical). Pooled per request, per
    #   tenant, and engine-wide; the engine pool runs the Page–Hinkley
    #   drafter-drift detector.
    # flight_record — bounded ring of per-round records (accept masks, TVD,
    #   pool/queue snapshot), dumped as a post-mortem JSON bundle on drift
    #   alarm, SLO breach, or engine crash.
    # slo — obs.sketch.SLOConfig; TTFT/TPOT observed per retired request into
    #   multi-window burn-rate trackers + O(1)-memory quantile sketches.
    quality: bool = False
    flight_record: bool = False
    flight_dir: str = "flight"
    slo: Optional[object] = None
    # sanitize — debug mode: every ``sanitize_every`` decode rounds (and once
    # at drain) sweep the paged-pool bookkeeping: refcount consistency
    # (``PagedKVPool.check_invariants`` with the prefix cache's node count),
    # host page-table mirror vs the pool's authoritative mapping, cross-row
    # page aliasing only with a matching refcount, and the shared-page
    # read-only contract (every shared page lies strictly below its decode
    # row's committed length). O(slots x pages) pure-host work, no device
    # syncs — cheap enough for ``benchmarks/run.py --smoke``.
    sanitize: bool = False
    sanitize_every: int = 8

    def __post_init__(self):
        if self.draft is None and self.draft_heads is None:
            raise ValueError("continuous engine is speculative-only; pass a "
                             "draft model or draft_heads")
        if self.draft is not None and self.draft_heads is not None:
            raise ValueError("pass either draft or draft_heads, not both")
        models = [(self.target, "target")]
        if self.draft is not None:
            models.append((self.draft, "draft"))
        for m, name in models:
            if not attention_only(m.cfg):
                raise ValueError(
                    f"{name} has recurrent layers; the paged KV pool supports "
                    "attention-only models")
            if m.cfg.num_codebooks > 1:
                raise ValueError("multi-codebook decode is not supported")
        if self.draft_heads is not None:
            if self.tree is not None:
                self.draft_heads.validate_tree(self.tree.depth)
            else:
                self.draft_heads.validate_chain(self.sd.gamma)
        if self.quality:
            # frozen SDConfig keys the jit cache: flipping quality here gives
            # this engine its own compiled round that also writes the buffers
            self.sd = replace(self.sd, quality=True)
        g = self.sd.gamma
        # tokens committable per decode round (accepted + pending) and the
        # per-row storage overshoot: a chain round writes at most gamma+1
        # positions past the committed length, a tree round writes its whole
        # node buffer (slots L .. L+N-1) before committing the root path.
        self._span = (self.tree.depth if self.tree else g) + 1
        self._slack = (self.tree.num_nodes + 1) if self.tree else (g + 2)
        self._row_cap = self.max_seq_len + self._slack
        max_pages = ceil_div(self._row_cap + self.prefill_chunk, self.page_size)
        if self.tree is not None:
            # tree verify runs the tree-attention kernel over the gathered
            # max_pages * page_size window: give it a width the kernel tiles
            max_pages = kv_window(max_pages * self.page_size,
                                  self.page_size) // self.page_size
        if self.num_pages is None:
            self.num_pages = 1 + self.max_batch * max_pages
        self.pool = PagedKVPool(self.num_pages, self.page_size, max_pages)
        self.prefix = (PrefixCache(self.pool, self.page_size)
                       if self.prefix_cache else None)
        self.scheduler = Scheduler(
            self.policy, aging_s=self.aging_s,
            prefix_probe=None if self.prefix is None else self._probe_prefix,
            registry=self.registry)
        self.telemetry = ServingTelemetry(registry=self.registry)
        self.stats: Dict[int, RequestStats] = {}
        self._tr = self.tracer if self.tracer is not None else NULL_TRACER
        if self._tr.enabled:
            _trace_gc(self)
        if self.registry is not None:
            # accepted-draft-tokens-per-round histogram: the live acceptance
            # signal the adaptive-speculation controller will consume
            self._m_accept = self.registry.histogram(
                "sd_accepted_per_round",
                buckets=tuple(float(i) for i in range(self._span + 1)),
                help="tokens committed per row per decode round")
        else:
            self._m_accept = None

        B, buf = self.max_batch, self._row_cap + self._span + 1
        # draft positions per round the quality buffers cover (tree rounds
        # report along the committed root path, depth-indexed like the chain)
        self._qdepth = self.tree.depth if self.tree is not None else g

        shape = (self.target, self.draft, B, buf, max_pages, self.num_pages,
                 self.page_size, self.kv_quant,
                 self._qdepth if self.quality else None)
        drafter = self.draft_heads if self.draft_heads is not None else self.draft
        # draft heads prefill only the target, whose hidden states seed them
        hidden = self.draft_heads is not None
        # across chips (the mesh the launch code installed, tensor parallel
        # over its model axis) every leaf is made straight into its layout:
        # the pools split by KV head, all else replicated. The donated round
        # and prefill steps return that layout and ``_pin`` restores it
        # after eager updates, so donated state aliases and no chip ever
        # holds a whole pool.
        mesh = shctx.get_mesh()
        self._layout = None
        if mesh is not None and mesh.size > 1:
            make, self._layout, *steps = _cached_mesh_programs(
                mesh, drafter, self.sd, self.tree, hidden, *shape)
            self._state = make()
        else:
            self._state = _init_state(*shape)
        self.quality_stats: Optional[QualityStats] = None
        self.tenant_quality: Dict[str, QualityStats] = {}
        if self.quality:
            self.quality_stats = QualityStats(depth=self._qdepth)
        self.recorder = (FlightRecorder(out_dir=self.flight_dir)
                         if self.flight_record else None)
        self.slo_tracker = SLOTracker(self.slo) if self.slo is not None else None
        self._d_params = (self.draft_head_params
                          if self.draft_heads is not None else self.draft_params)
        self._slots = [_Slot() for _ in range(B)]
        self._lengths_h = np.zeros((B,), np.int64)
        self._table_h = np.zeros((B, max_pages), np.int32)
        # device-side committed-window gather: indexing tokens with host np
        # index arrays would be an implicit h2d transfer per round (and a
        # transfer_guard violation); this keeps the gather on device so the
        # round's ONLY host sync is the single fetch device_get.
        self._win_fn = _cached_window_gather(self._span)
        self._key = jax.random.PRNGKey(0)
        if self._layout is not None:
            self._round, self._d_step, self._t_step = steps
            self._key = jax.device_put(self._key, self._layout["lengths"])
        else:
            # fused round with the state donated: the engine rebinds
            # self._state every round and reads only the round's *output*
            # leaves afterwards, so XLA aliases every state buffer
            # input->output (cache commits are in-place; one copy of the
            # pool, not two)
            self._round = _cached_round_donated(drafter, self.target,
                                                self.sd, self.tree)
            lc = self.sd.long_context
            self._d_step = (None if hidden
                            else _cached_decode(self.draft, lc, False))
            self._t_step = _cached_decode(self.target, lc, hidden)
        self._admit_seq = 0
        self._t0: Optional[float] = None
        self._last_sanitize = 0

    # ---------------------------------------------------------------- clock
    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    def _pin(self):
        """Across chips, put every state leaf back in the layout the donated
        programs were compiled for (a no-op for a leaf already there): eager
        updates leave their output's layout to the compiler."""
        if self._layout is not None:
            self._state = jax.tree.map(
                lambda x, s: x if x.sharding == s else jax.device_put(x, s),
                self._state, self._layout)

    # ---------------------------------------------------------------- submit
    def _worst_case_tokens(self, req: ServeRequest) -> int:
        plen = len(req.prompt)
        padded = ceil_div(plen, self.prefill_chunk) * self.prefill_chunk
        return max(padded, plen + req.max_new_tokens + self._slack)

    def submit(self, req: ServeRequest):
        plen = len(req.prompt)
        if plen + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"request {req.request_id}: prompt {plen} + max_new "
                f"{req.max_new_tokens} exceeds max_seq_len {self.max_seq_len}")
        need = self.pool.pages_needed(self._worst_case_tokens(req))
        if need > min(self.num_pages - 1, self.pool.max_pages_per_seq):
            # would never be admissible even into an empty pool -> the
            # engine would otherwise spin on it forever
            raise ValueError(
                f"request {req.request_id}: needs {need} KV pages; the pool "
                f"can ever free {min(self.num_pages - 1, self.pool.max_pages_per_seq)}")
        # simulated arrivals are submitted early; latency clocks start at the
        # later of now and the request's nominal arrival
        stats = RequestStats(
            request_id=req.request_id,
            submit_time_s=max(self._now(), req.arrival_time_s),
            prompt_tokens=plen)
        if self.quality:
            stats.quality = QualityStats(depth=self._qdepth)
        self.stats[req.request_id] = stats
        # request lifecycle track, stamped with the SAME clock RequestStats
        # uses (engine-relative -> absolute perf_counter) so TTFT/TPOT
        # reconstructed from the trace match the stats exactly
        self._tr.async_begin("request", req.request_id,
                             ts=self._t0 + stats.submit_time_s,
                             prompt_tokens=plen,
                             max_new_tokens=req.max_new_tokens)
        self.scheduler.submit(req)

    # ---------------------------------------------------------------- admit
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s.state == "free":
                return i
        return None

    def _probe_prefix(self, req: ServeRequest) -> int:
        """Scheduler hook: stamp the request's longest cached prefix.

        The hit is clamped to prompt_len - 1 — the last prompt token is
        always re-prefilled because its logits seed the first sample. On a
        page-aligned full-prompt hit that token lives inside the last shared
        page, which is what triggers the tail-page COW in ``_admit``."""
        hit_tokens, pages = self.prefix.match(req.prompt)
        req.prefix_hit = min(hit_tokens, len(req.prompt) - 1)
        req.prefix_pages = list(pages)
        return req.prefix_hit

    @staticmethod
    def _needs_cow(req: ServeRequest, page_size: int) -> bool:
        """Resumed prefill writes inside the last shared page?"""
        return bool(req.prefix_pages) and \
            len(req.prefix_pages) * page_size > req.prefix_hit

    def _evict_one(self, protect) -> bool:
        """Drop the LRU prefix-cache leaf; invalidate pages actually freed."""
        freed = self.prefix.evict_lru_leaf(protect=protect)
        if freed is None:
            return False
        if freed:
            st = self._state
            if "d_cache" in st:
                st["d_cache"] = invalidate_pages(st["d_cache"], freed)
            st["t_cache"] = invalidate_pages(st["t_cache"], freed)
        return True

    def _can_admit(self, req: ServeRequest) -> bool:
        if self._free_slot() is None:
            return False
        need = self._worst_case_tokens(req)
        if self.prefix is None:
            return self.pool.can_alloc(need)
        n_shared = len(req.prefix_pages)
        cow = self._needs_cow(req, self.page_size)

        def fits():
            return self.pool.can_alloc_shared(need, n_shared, cow)

        # under memory pressure, cached-but-idle prefixes yield to live work
        # (LRU leaf first); pages still mapped by running rows only lose the
        # cache reference. The just-matched pages are protected so eviction
        # cannot free what this admission is about to map.
        while not fits() and self._evict_one(protect=req.prefix_pages):
            pass
        return fits()

    def _admit(self, req: ServeRequest, now: float):
        i = self._free_slot()
        shared = req.prefix_pages if self.prefix is not None else []
        self.pool.alloc(i, self._worst_case_tokens(req), shared=shared)
        if self.prefix is not None and self._needs_cow(req, self.page_size):
            # the resumed prefill's first write lands inside the last shared
            # page: give this row a private, bit-identical copy first
            old, new = self.pool.cow_page(i, len(shared) - 1)
            if old != new:
                st = self._state
                if "d_cache" in st:
                    st["d_cache"] = copy_pages(st["d_cache"], [old], [new])
                st["t_cache"] = copy_pages(st["t_cache"], [old], [new])
                self.prefix.tel.cow_copies += 1
        self._table_h[i] = self.pool.table_row(i)
        slot = self._slots[i]
        plen = len(req.prompt)
        slot.state, slot.req = "prefill", req
        slot.prompt_len, slot.target_len = plen, plen + req.max_new_tokens
        slot.prefill_pos, slot.emitted = 0, 0
        slot.admit_seq, self._admit_seq = self._admit_seq, self._admit_seq + 1
        slot.stats = self.stats[req.request_id]
        slot.stats.admit_time_s = now
        self._tr.async_instant("admit", req.request_id, ts=self._t0 + now,
                               slot=i, prefix_hit=req.prefix_hit)
        if self.prefix is not None:
            # resume chunked prefill at the hit boundary: the shared pages
            # already hold positions [0, prefix_hit) for both models
            slot.prefill_pos = req.prefix_hit
            slot.stats.prefix_hit_tokens = req.prefix_hit
            tel = self.prefix.tel
            tel.lookups += 1
            tel.hits += int(req.prefix_hit > 0)
            tel.hit_tokens += req.prefix_hit
            tel.prompt_tokens += plen
        st = self._state
        st["tokens"] = st["tokens"].at[i, :plen].set(
            jnp.asarray(req.prompt, jnp.int32))
        st["page_table"] = jnp.asarray(self._table_h)
        self.telemetry.admitted += 1

    # ---------------------------------------------------------------- prefill
    def _prefill_one_chunk(self, i: int):
        self._pin()
        slot, st = self._slots[i], self._state
        req, C = slot.req, self.prefill_chunk
        start = slot.prefill_pos
        chunk = np.zeros((1, C), np.int32)
        real = min(C, slot.prompt_len - start)
        chunk[0, :real] = np.asarray(req.prompt[start:start + real], np.int32)
        toks = jnp.asarray(chunk)
        positions = jnp.arange(start, start + C, dtype=jnp.int32)[None]
        table = jnp.asarray(self._table_h[i:i + 1])
        if self.draft_heads is None:
            _, st["d_cache"] = self._d_step(self.draft_params, toks, positions,
                                            st["d_cache"], page_table=table)
            logits, st["t_cache"] = self._t_step(self.target_params, toks,
                                                 positions, st["t_cache"],
                                                 page_table=table)
            hid = None
        else:
            # heads: only the target prefils; its hidden states seed h_feat
            logits, st["t_cache"], hid = self._t_step(
                self.target_params, toks, positions, st["t_cache"],
                page_table=table)
        slot.prefill_pos = start + real
        self.telemetry.prefill_chunks += 1
        if slot.prefill_pos < slot.prompt_len:
            return None
        if self.prefix is not None:
            # register the prompt's full pages (all positions < prompt_len,
            # so they are immutable from here on — decode and speculative
            # invalidation only address storage positions >= committed length)
            n_full = slot.prompt_len // self.page_size
            if n_full > 0:
                self.prefix.insert(np.asarray(req.prompt[:n_full * self.page_size]),
                                   [int(p) for p in self._table_h[i][:n_full]])
        # prompt fully fed: drop padding garbage, sample the first token
        limit = jnp.asarray([slot.prompt_len - 1], jnp.int32)
        if self.draft_heads is None:
            st["d_cache"] = trim_paged_cache(st["d_cache"], table, limit)
        st["t_cache"] = trim_paged_cache(st["t_cache"], table, limit)
        self._key, k = jax.random.split(self._key)
        last = slot.prompt_len - 1 - start
        if hid is not None:
            st["h_feat"] = st["h_feat"].at[i].set(hid[0, last])
        p = probs_from_logits(logits[0, last], self.sd.temperature, self.sd.top_p)
        tok = sample_from_probs(k, p)
        st["pending"] = st["pending"].at[i].set(tok)
        st["lengths"] = st["lengths"].at[i].set(slot.prompt_len)
        st["active"] = st["active"].at[i].set(True)
        self._lengths_h[i] = slot.prompt_len
        slot.state = "decode"
        slot.stats.first_token_time_s = self._now()
        self._tr.async_instant("first_token", slot.req.request_id,
                               ts=self._t0 + slot.stats.first_token_time_s)
        with self._tr.span("prefill_wait"):
            return int(jax.device_get(tok))

    # ---------------------------------------------------------------- step
    def step(self) -> List[tuple]:
        """One engine iteration: admit; one prefill chunk; one decode round.

        Returns a list of events: ("token", request_id, np.ndarray of new
        token ids) and ("finish", request_id, Result).
        """
        with self._tr.span("engine_step"):
            return self._step()

    def _step(self) -> List[tuple]:
        now = self._now()
        events: List[tuple] = []
        did_work = False
        with self._tr.span("admit"):
            while True:
                req = self.scheduler.pop_admissible(now, self._can_admit)
                if req is None:
                    break
                self._admit(req, now)
                did_work = True

        prefilling = [i for i, s in enumerate(self._slots)
                      if s.state == "prefill"]
        if prefilling:
            i = min(prefilling, key=lambda j: self._slots[j].admit_seq)
            with self._tr.span("prefill_chunk", slot=i):
                first_tok = self._prefill_one_chunk(i)
            if first_tok is not None:
                events.extend(self._emit(i, np.asarray([first_tok], np.int64)))
            did_work = True

        if bool(np.any([s.state == "decode" for s in self._slots])):
            with self._tr.span("decode_round"):
                events.extend(self._decode_round())
            did_work = True

        if self.sanitize and self.telemetry.decode_rounds >= \
                self._last_sanitize + self.sanitize_every:
            self._last_sanitize = self.telemetry.decode_rounds
            self._sanitize_check()

        if not did_work:   # idle ticks (waiting on arrivals) don't skew telemetry
            time.sleep(5e-4)
            return events
        with self._tr.span("telemetry"):
            qd = self.scheduler.ready_depth(self._now())
            act = sum(s.state == "decode" for s in self._slots)
            self.telemetry.sample(qd, act, self.pool.num_free,
                                  self.pool.shared_page_fraction())
            if self._tr.enabled:
                self._tr.counter("queue_depth", qd)
                self._tr.counter("active_rows", act)
                self._tr.counter("free_pages", self.pool.num_free)
            if self.registry is not None:
                if self.prefix is not None:
                    self.prefix.tel.emit(self.registry)
                if self.quality_stats is not None:
                    self.quality_stats.emit(self.registry)
                if self.slo_tracker is not None:
                    self.slo_tracker.emit(self.registry)
                if self.metrics_out and \
                        self.telemetry.steps % self.metrics_every == 0:
                    self.registry.write_snapshot(self.metrics_out)
        return events

    def _decode_round(self) -> List[tuple]:
        tr = self._tr
        with tr.span("round_dispatch"):
            self._pin()
            st = self._state
            self._key, kr = jax.random.split(self._key)
            old_len = self._lengths_h.copy()
            # device copy of the pre-round lengths for the window gather
            # below: a distinct buffer, so donating st["lengths"] into the
            # round cannot invalidate it, and no host index arrays ever
            # cross to the device
            base_dev = st["lengths"].copy()
            t_round = time.perf_counter()
            st, n_acc = self._round(self._d_params, self.target_params, st,
                                    kr)
            self._state = st
            # one transfer: lengths + committed windows + the fresh pending
            # token (+ the quality buffers when enabled — they ride the
            # same sync)
            win = self._win_fn(st["tokens"], base_dev)
            fetch = [st["lengths"], win, st["pending"]]
            if self.quality:
                q = st["qual"]
                fetch += [q["tvd"], q["ent"], q["acc"], q["drafted"]]
        with tr.span("round_wait"):
            got = [np.asarray(a) for a in jax.device_get(tuple(fetch))]
        lengths_h, win_h, pending_h = got[:3]
        qual_h = got[3:] if self.quality else None
        # the device_get above synchronizes, so this spans the real round
        round_dt = time.perf_counter() - t_round
        self._lengths_h = lengths_h.astype(np.int64)
        self.telemetry.decode_rounds += 1
        # pages the round's attention reads per active row: its committed
        # length plus the positions the round writes, against the table
        live = np.array([s.state == "decode" for s in self._slots])
        self.telemetry.count_kv_pages(
            np.sum((old_len[live] + self._span + self.page_size - 1)
                   // self.page_size),
            int(live.sum()) * self._table_h.shape[1])

        events: List[tuple] = []
        with tr.span("round_emit"):
            retiring: List[int] = []
            for i, slot in enumerate(self._slots):
                if slot.state != "decode":
                    continue
                n_committed = int(lengths_h[i] - old_len[i])
                slot.stats.sd.update(n_committed)
                # per-request wall time: every active row paid this round
                # (pooled tokens_per_s on merged stats was silently 0 before)
                slot.stats.sd.wall_time_s += round_dt
                if self._m_accept is not None:
                    self._m_accept.observe(n_committed)
                    self.registry.counter("sd_tokens_total",
                                          "committed tokens").inc(n_committed)
                    self.registry.counter("sd_blocks_total",
                                          "speculation rounds").inc()
                # stream: window[0] is the previous pending (already
                # emitted); the new pending is available now and always
                # commits next round.
                fresh = np.concatenate([win_h[i, 1:n_committed],
                                        [pending_h[i]]]).astype(np.int64)
                events.extend(self._emit(i, fresh))
                if lengths_h[i] >= slot.target_len:
                    retiring.append(i)
            if self.quality:
                self._pool_quality(qual_h)
            if self.recorder is not None:
                self._record_round(qual_h, old_len, lengths_h)
            for i in retiring:
                with tr.span("retire"):
                    events.append(self._retire(i))
        return events

    def _pool_quality(self, qual_h):
        """Fold this round's device quality buffers into the per-request,
        per-tenant, and engine-wide accumulators; a drift alarm on the
        engine pool triggers a flight-recorder dump."""
        tvd_h, ent_h, acc_h, drafted_h = qual_h
        rows = [i for i, s in enumerate(self._slots) if s.state == "decode"]
        if not rows:
            return
        for i in rows:
            rq = self._slots[i].stats.quality
            if rq is not None:
                rq.update_round(tvd_h[i], ent_h[i], acc_h[i], drafted_h[i])
        by_tenant: Dict[str, List[int]] = {}
        for i in rows:
            tenant = getattr(self._slots[i].req, "tenant", "") or ""
            by_tenant.setdefault(tenant, []).append(i)
        for tenant, idxs in by_tenant.items():
            qs = self.tenant_quality.get(tenant)
            if qs is None:
                qs = self.tenant_quality[tenant] = QualityStats(
                    depth=self._qdepth)
            qs.update_round(tvd_h[idxs], ent_h[idxs], acc_h[idxs],
                            drafted_h[idxs])
        alarm = self.quality_stats.update_round(
            tvd_h[rows], ent_h[rows], acc_h[rows], drafted_h[rows])
        if alarm and self.recorder is not None:
            self.recorder.dump("drift_alarm", context={
                "decode_rounds": self.telemetry.decode_rounds,
                "quality": self.quality_stats.snapshot()})

    def _record_round(self, qual_h, old_len, lengths_h):
        """One bounded flight-recorder entry per decode round."""
        slots = {}
        for i, s in enumerate(self._slots):
            if s.state != "decode":
                continue
            rec = {"request_id": s.req.request_id,
                   "committed": int(lengths_h[i] - old_len[i])}
            if qual_h is not None:
                tvd_h, _, acc_h, drafted_h = qual_h
                d = drafted_h[i].astype(bool)
                rec["accept"] = [bool(b) for b in acc_h[i]]
                rec["mean_tvd"] = (float(tvd_h[i][d].mean())
                                   if d.any() else None)
            slots[i] = rec
        entry = {"slots": slots,
                 "queue_depth": self.scheduler.ready_depth(self._now()),
                 "free_pages": self.pool.num_free,
                 "active_rows": len(slots)}
        self.recorder.record_round(**entry)

    # ---------------------------------------------------------------- sanitize
    def _sanitize_check(self):
        """Debug-mode paged-pool invariant sweep (``sanitize=True``).

        Pure host-side bookkeeping checks — no device syncs:
          1. ``PagedKVPool.check_invariants`` with the prefix cache's live
             node count (refcounts == slot mappings + cache references,
             free list disjoint from live pages, null page never handed out);
          2. the engine's host page-table mirror (what the jitted round
             reads) matches the pool's authoritative per-slot mapping;
          3. a physical page mapped by k rows carries a refcount >= k
             (cross-row aliasing only via real shared references);
          4. shared pages (refcount > 1) mapped by a decode row lie strictly
             below that row's committed length — the read-only contract that
             makes COW-free decode writes safe.
        Raises AssertionError naming the slot/page on violation.
        """
        cache_refs = self.prefix.num_nodes if self.prefix is not None else 0
        self.pool.check_invariants(cache_refs=cache_refs)
        mapped_by: Dict[int, List[int]] = {}
        for i, slot in enumerate(self._slots):
            row = self.pool.table_row(i)
            if not np.array_equal(row, self._table_h[i]):
                raise AssertionError(
                    f"sanitize: slot {i} host table mirror "
                    f"{self._table_h[i].tolist()} diverged from pool mapping "
                    f"{row.tolist()}")
            for logical, page in enumerate(row):
                if page != 0:
                    mapped_by.setdefault(int(page), []).append(i)
            if slot.state == "decode":
                committed = int(self._lengths_h[i])
                for logical, page in enumerate(row):
                    if page != 0 and self.pool.page_ref(int(page)) > 1 and \
                            (logical + 1) * self.page_size > committed:
                        raise AssertionError(
                            f"sanitize: slot {i} maps shared page {page} at "
                            f"logical index {logical} covering positions up "
                            f"to {(logical + 1) * self.page_size} but has "
                            f"only committed {committed} — decode would "
                            f"write a shared page")
        for page, rows in mapped_by.items():
            if len(rows) > 1 and self.pool.page_ref(page) < len(rows):
                raise AssertionError(
                    f"sanitize: page {page} mapped by rows {rows} with "
                    f"refcount {self.pool.page_ref(page)} < {len(rows)}")

    def _emit(self, i: int, toks: np.ndarray) -> List[tuple]:
        slot = self._slots[i]
        room = (slot.target_len - slot.prompt_len) - slot.emitted
        toks = toks[:max(room, 0)]
        if toks.size == 0:
            return []
        slot.emitted += int(toks.size)
        slot.stats.new_tokens = slot.emitted
        if slot.req.on_token is not None:
            slot.req.on_token(slot.req.request_id, toks)
        return [("token", slot.req.request_id, toks)]

    def _retire(self, i: int) -> tuple:
        slot, st = self._slots[i], self._state
        row = np.asarray(jax.device_get(st["tokens"][i]))
        out = row[slot.prompt_len:slot.target_len]
        slot.stats.finish_time_s = self._now()
        slot.stats.new_tokens = slot.target_len - slot.prompt_len
        if self.slo_tracker is not None:
            breached = self.slo_tracker.observe(slot.stats.ttft_s,
                                                slot.stats.tpot_s)
            if breached and self.recorder is not None:
                self.recorder.dump("slo_breach", context={
                    "request_id": slot.req.request_id,
                    "metrics": breached,
                    "slo": self.slo_tracker.snapshot()})
        # only pages whose refcount hit zero leave the pool — a prefix page
        # still backing other rows (or held by the prefix cache) keeps its
        # contents and stays mapped for future hits
        freed = self.pool.free_slot(i)
        if freed:
            if "d_cache" in st:
                st["d_cache"] = invalidate_pages(st["d_cache"], freed)
            st["t_cache"] = invalidate_pages(st["t_cache"], freed)
        self._table_h[i] = 0
        st["page_table"] = jnp.asarray(self._table_h)
        st["active"] = st["active"].at[i].set(False)
        result = Result(request_id=slot.req.request_id, tokens=out,
                        tau=slot.stats.sd.tau,
                        wall_time_s=slot.stats.finish_time_s
                        - slot.stats.submit_time_s)
        self._tr.async_end("request", slot.req.request_id,
                           ts=self._t0 + slot.stats.finish_time_s,
                           new_tokens=slot.stats.new_tokens,
                           tau=round(slot.stats.sd.tau, 4))
        req = slot.req
        self._slots[i] = _Slot()
        self.telemetry.completed += 1
        if req.on_finish is not None:
            req.on_finish(result)
        return ("finish", result.request_id, result)

    # ---------------------------------------------------------------- drivers
    def has_work(self) -> bool:
        return len(self.scheduler) > 0 or any(
            s.state != "free" for s in self._slots)

    def stream(self):
        """Generator yielding events until the engine drains. With the
        flight recorder on, an exception escaping the loop dumps the ring
        (reason "crash") before propagating — the post-mortem survives."""
        try:
            while self.has_work():
                for ev in self.step():
                    yield ev
        except Exception as e:
            if self.recorder is not None:
                ctx = {"error": f"{type(e).__name__}: {e}"}
                if self.quality_stats is not None:
                    ctx["quality"] = self.quality_stats.snapshot()
                self.recorder.dump("crash", context=ctx)
            raise

    def run(self) -> List[Result]:
        out = [ev[2] for ev in self.stream() if ev[0] == "finish"]
        if self.sanitize:
            self._sanitize_check()   # drained end state: no leaked pages
        self.finalize_metrics()
        return out

    def finalize_metrics(self):
        """Final registry snapshot at drain (periodic ones are step-gated)."""
        if self.registry is not None and self.metrics_out:
            self.registry.write_snapshot(self.metrics_out)

    def serve(self, requests: Sequence, key=None) -> List[Result]:
        """Static-engine-compatible entry point (ignores ``key``: at
        temperature 0 sampling is deterministic; stochastic parity across
        engines is not defined under membership changes)."""
        for r in requests:
            if isinstance(r, Request):
                r = ServeRequest(prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 request_id=r.request_id)
            self.submit(r)
        return sorted(self.run(), key=lambda r: r.request_id)
