"""Paged attention Pallas kernel: attend through each row's page table.

Decode, speculative verify and chunked prefill run against one shared paged
KV pool (``serving.kv_pool``). The gather path in
``models.attention.paged_decode_attention`` builds a per-row view of every
page the table can hold (``max_pages`` x ``page`` slots) and scores all of
it. This kernel reads only the pages a row has written: K/V pools stay in
HBM and each row's live pages are DMA'd into VMEM a block of pages at a
time, one block landing while the one before it is scored, with online
softmax in float32 over the blocks.

Tiling (``tiling``), from the call's shapes alone: a block holds as many
pages as make ``BLOCK_BYTES`` of K and V, since each block pays a fixed
wait on top of its pages' copies whatever it moves; 8 pages of a pool of
8 heads, 64 of a pool of one head a chip (4-KB pages). A grid step takes
all ``T * G`` query rows of a KV head when its tiles fit ``VMEM_BYTES``,
and otherwise the largest even share of them that fits: each step reads
the row's pages again, so a prefill chunk takes as few steps as fit. Each
page is two DMAs, whose scalar bookkeeping is what a small page pays most
for, so a block copies and waits on its live pages alone, in a loop as
long as they are.

Layout: the pools are the model's stacked per-layer pools
``(L, P, page, Hkv, hd)`` with the layer as a scalar operand, so the layer
scan hands the kernel the loop's own buffer and nothing is sliced out of
it. The queries of one row and KV head, ``R = T * G`` rows (GQA), are
scored in one matmul per block. A pool of one 16-bit KV head (one chip's
share of a pool split by head) is read as ``(L, P, page, hd)``: XLA keeps
the unit head axis outside the tiled ``(page, hd)`` pair, so dropping it is
a bitcast, and each page is one ``page x hd`` block, with no second head.

What a row reads: logical pages ``first .. end - 1`` where ``end`` is one
past the page holding the row's largest query position, and ``first`` is
0, or with a sliding window the block holding the window's first page.
Pages past ``end`` hold only positions above every query, so skipping them
is exact. A row whose table is all null (an inactive row) reads nothing and
returns zeros.

Validity is the gather path's mask, from the pool's ``page_pos`` read
through the same table: slot written, position ``<= `` the query's, entries
of the null page 0 masked, and the window if set. So the result equals the
gather path's up to summation order. The slot positions are gathered
through the table outside the kernel (4 bytes a slot against
``2 * Hkv * hd`` bytes of K/V) and the slots of pages past ``end`` read as
unwritten there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 512 * 1024    # K + V one DMA block moves (8 pages of 32 KB)
VMEM_BYTES = 12 * 1024 * 1024   # the tiles' share of 16 MiB of scoped VMEM
SCORE_COPIES = 4            # f32 (rows, slots) values live at once a head


def vmem_bytes(rows: int, npb: int, page: int, hkv: int, hd: int,
               itemsize: int, max_pages: int) -> int:
    """VMEM one grid step of ``rows`` query rows and ``npb``-page blocks
    takes: the pipelined q, query-position, slot-position and output tiles
    (two of each), the landing buffers, the softmax state, and the score
    tiles of one head. Columns of width 1 fill a 128-lane row."""
    slots = npb * page
    nb = -(-max_pages // npb)
    tiles = 2 * (hkv * rows * hd * (itemsize + 4)    # q, f32 output
                 + rows * 128 * 4 + nb * slots * 4)  # query, slot positions
    buffers = 2 * 2 * slots * hkv * hd * itemsize
    softmax = 2 * hkv * rows * 128 * 4               # m, l
    return tiles + buffers + softmax + SCORE_COPIES * rows * slots * 4


def tiling(page: int, hkv: int, hd: int, itemsize: int, rows: int,
           max_pages: int, pages_per_block=None):
    """(pages a DMA block, query rows a grid step) for a call's shapes.

    A block moves ``BLOCK_BYTES`` of K and V whatever a page holds, so a
    pool of one head a chip (4-KB pages) takes 64 pages a block where one
    of 8 heads takes 8. All
    ``rows`` query rows of a KV head go in one grid step when their tiles
    fit ``VMEM_BYTES``, so a row's K/V is read once; otherwise the largest
    whole share of them that fits (a multiple of 16 rows, the bf16
    sublane tile). ``pages_per_block`` overrides the first."""
    page_bytes = 2 * page * hkv * hd * itemsize
    npb = pages_per_block or max(1, BLOCK_BYTES // page_bytes)
    npb = min(npb, max_pages)
    for parts in range(1, rows + 1):
        rb = rows // parts
        if rows % parts or (parts > 1 and rb % 16):
            continue
        if vmem_bytes(rb, npb, page, hkv, hd, itemsize,
                      max_pages) <= VMEM_BYTES:
            return npb, rb
    return npb, rows


def supports(num_kv_heads: int, dtype) -> bool:
    """Can the kernel read a pool of ``dtype`` with ``num_kv_heads`` heads?
    Heads narrower than 32 bits are loaded a 32-bit row at a time
    (``_heads``), so their count must fill whole rows; a single 16-bit head
    is read without its head axis."""
    size = jnp.dtype(dtype).itemsize
    return num_kv_heads % max(1, 4 // size) == 0 or (
        num_kv_heads == 1 and size == 2)


def _live_span(page_table, pos, page: int, window, pages_per_block: int):
    """Per row (B, 2) int32: the first block to read and one past the last
    live page. ``page_table`` (B, max_pages) has inactive rows all null."""
    max_pages = page_table.shape[1]
    end = jnp.minimum(jnp.max(pos, axis=1) // page + 1, max_pages)
    end = jnp.where(jnp.any(page_table != 0, axis=1), end, 0)
    if window is None:
        first = jnp.zeros_like(end)
    else:
        lo = jnp.maximum(jnp.min(pos, axis=1) - window + 1, 0)
        first = jnp.minimum(lo // page, end) // pages_per_block
    return jnp.stack([first, end], axis=1).astype(jnp.int32)


def _heads(kref, vref):
    """Yield (head, K, V) of one block, each (npb * page, hd), from VMEM
    blocks (npb, page, Hkv, hd), or (npb, page, hd) for a single head.
    16-bit heads pack two to a 32-bit row: one sublane-strided 32-bit load
    gives a pair, split by shifts."""
    if kref.ndim == 3:
        npb, page, hd = kref.shape
        yield 0, kref[...].reshape(npb * page, hd), \
            vref[...].reshape(npb * page, hd)
        return
    npb, page, hkv, hd = kref.shape
    S = npb * page
    if jnp.dtype(kref.dtype).itemsize == 4:
        for h in range(hkv):
            yield (h, kref[:, :, h, :].reshape(S, hd),
                   vref[:, :, h, :].reshape(S, hd))
        return
    kw = kref.reshape(S * hkv, hd).bitcast(jnp.uint32)
    vw = vref.reshape(S * hkv, hd).bitcast(jnp.uint32)

    def split(w):
        lo = pltpu.bitcast(w << 16, jnp.float32).astype(kref.dtype)
        hi = pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
        return lo, hi.astype(kref.dtype)

    for pair in range(hkv // 2):
        rows = pl.ds(pair, S, stride=hkv // 2)
        k0, k1 = split(kw[rows, :])
        v0, v1 = split(vw[rows, :])
        yield 2 * pair, k0, v0
        yield 2 * pair + 1, k1, v1


def _kernel(layer_ref, table_ref, span_ref,                   # SMEM
            q_ref, qpos_ref, cp_ref, k_hbm, v_hbm,            # inputs
            o_ref,                                            # output
            kbuf, vbuf, sems, m_ref, l_ref, slot0_ref,        # scratch
            *, npb, max_pages, scale, softcap, window):
    b, i = pl.program_id(0), pl.program_id(1)
    nrows, nq = pl.num_programs(0), pl.num_programs(1)
    _, hkv, _, _ = q_ref.shape
    page = kbuf.shape[2]
    layer = layer_ref[0]

    def span(row):              # first block, end page, blocks to read
        first, end = span_ref[2 * row], span_ref[2 * row + 1]
        return first, end, jnp.maximum(end - first * npb + npb - 1, 0) // npb

    pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))

    def live(blk, end):         # pages of block ``blk`` below ``end``
        return jnp.clip(end - blk * npb, 0, npb)

    def each_page(row, slot, blk, end, act):
        """``act`` on the K and V copies of each live page of a block."""
        at = row * max_pages + blk * npb

        def page_copies(p, carry):
            phys = table_ref[at + p]
            for hbm, buf, kv in pools:
                act(pltpu.make_async_copy(hbm.at[layer, phys],
                                          buf.at[slot, p], sems.at[kv, slot]))
            return carry
        jax.lax.fori_loop(0, live(blk, end), page_copies, 0)

    def start(row, slot, blk, end):
        each_page(row, slot, blk, end, lambda c: c.start())

    def wait(row, slot, blk, end):
        each_page(row, slot, blk, end, lambda c: c.wait())

    first, end, nblk = span(b)
    # the grid runs in order; each step's first block is copied in while
    # the step before it computes its last, into the slot it leaves free
    nxt = jnp.minimum(jnp.where(i + 1 < nq, b, b + 1), nrows - 1)
    nfirst, nend, nnblk = span(nxt)
    has_next = ((i + 1 < nq) | (b + 1 < nrows)) & (nnblk > 0)

    @pl.when((b == 0) & (i == 0))
    def _first_step():
        # pages past ``end`` are never copied in: their slots keep what the
        # buffer held, and a zero probability times a NaN would still be NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot0_ref[0] = 0

        @pl.when(nblk > 0)
        def _():
            start(b, 0, first, end)

    base = slot0_ref[0]
    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    qp = qpos_ref[0]                                     # (R, 1)
    # bf16 operands: exact products, f32 sums; f32 pools keep f32 passes
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    @pl.when((nblk == 0) & has_next)
    def _pass_on():
        start(nxt, base, nfirst, nend)

    def block(j, carry):
        slot = (base + j) % 2
        blk = first + j

        @pl.when(j + 1 < nblk)
        def _next_block():
            start(b, 1 - slot, blk + 1, end)

        @pl.when((j + 1 == nblk) & has_next)
        def _next_step():
            start(nxt, 1 - slot, nfirst, nend)

        wait(b, slot, blk, end)
        cp = cp_ref[0, pl.ds(blk, 1), :]                 # (1, S)
        valid = (cp >= 0) & (cp <= qp)                   # (R, S)
        if window is not None:
            valid &= cp > qp - window
        for h, k, v in _heads(kbuf.at[slot], vbuf.at[slot]):
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    precision=prec,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]                            # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            o_ref[0, h] = o_ref[0, h] * alpha + jnp.dot(
                p.astype(v.dtype), v, precision=prec,
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    slot0_ref[0] = (base + nblk) % 2
    for h in range(hkv):
        o_ref[0, h] = o_ref[0, h] / jnp.maximum(l_ref[h], 1e-30)


def paged_attention(q, k_pool, v_pool, page_pos, page_table, pos, layer=0,
                    softcap=None, window=None, interpret=True,
                    pages_per_block=None):
    """Attention of new queries over a paged KV pool, read in place.

    q: (B, T, H, hd) queries (RoPE applied); k_pool/v_pool:
    (L, P, page, Hkv, hd) stacked per-layer pools; page_pos: (L, P, page)
    int32 position written in each slot (-1 empty); page_table:
    (B, max_pages) int32, inactive rows all 0 (the null page); pos: (B, T)
    int32 query positions; layer: which of the L pools (traced is fine).
    The new tokens must already be scattered into the pools.
    ``pages_per_block`` overrides the rule's block size (``tiling``).

    Returns (B, T, H, hd) float32. Oracle: ``ref.ref_paged_attention``.
    """
    B, T, H, hd = q.shape
    _, _, page, hkv, _ = k_pool.shape
    G = H // hkv
    R = T * G
    max_pages = page_table.shape[1]
    npb, rb = tiling(page, hkv, hd, jnp.dtype(k_pool.dtype).itemsize, R,
                     max_pages, pages_per_block)
    nb = pl.cdiv(max_pages, npb)
    page_table = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if hkv == 1 and jnp.dtype(k_pool.dtype).itemsize == 2:
        k_pool = k_pool.reshape(k_pool.shape[:3] + (hd,))
        v_pool = v_pool.reshape(v_pool.shape[:3] + (hd,))
    buf = (2, npb) + k_pool.shape[2:]

    span = _live_span(page_table, pos, page, window, npb)
    # slot positions through the table; null entries and pages past the
    # live end read as unwritten, and the table is padded to whole blocks
    lp = jnp.arange(nb * npb)
    tab = jnp.pad(page_table, ((0, 0), (0, nb * npb - max_pages)))
    cp = jnp.take(page_pos, layer[0], axis=0)[tab]       # (B, nb*npb, page)
    dead = (tab == 0) | (lp[None, :] >= span[:, 1:2])
    cp = jnp.where(dead[:, :, None], -1, cp).reshape(B, nb, npb * page)

    qg = q.reshape(B, T, hkv, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, hkv, R, hd).astype(k_pool.dtype)
    qpos = jnp.repeat(pos, G, axis=1)[:, :, None]        # row t*G+g <- t

    kernel = functools.partial(
        _kernel, npb=npb, max_pages=max_pages, scale=1.0 / math.sqrt(hd),
        softcap=softcap, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, R // rb),
            in_specs=[
                pl.BlockSpec((1, hkv, rb, hd), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, rb, 1), lambda b, i, *_: (b, i, 0)),
                pl.BlockSpec((1, nb, npb * page), lambda b, i, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hkv, rb, hd),
                                   lambda b, i, *_: (b, 0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM(buf, k_pool.dtype),
                pltpu.VMEM(buf, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, rb, 1), jnp.float32),
                pltpu.VMEM((hkv, rb, 1), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, hkv, R, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(layer, page_table.reshape(-1), span.reshape(-1), qg, qpos, cp,
      k_pool, v_pool)
    out = out.reshape(B, hkv, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, hd)
