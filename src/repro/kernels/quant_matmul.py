"""Fused dequantize-matmul Pallas kernel (weight-only quantization).

Decode is memory-bound: every step reads every weight byte once, so the
win from int8/int4 weights is exactly the byte reduction — but only if the
dequantize happens *in kernel*, after the quantized tile has been DMA'd to
VMEM. This kernel streams (Kt, Nt) quantized weight tiles HBM->VMEM, widens
them on-chip, and accumulates ``x @ W`` in an fp32 VMEM scratch; the
full-precision weight matrix never exists in HBM.

Two layouts, matching ``repro.quant.qweight.QWeight``:

  int8  : q (K, N) int8, scale (1, N) fp32 per-out-channel. Dequantization
          commutes with the K-reduction (the scale is constant along K), so
          the kernel accumulates integer-valued fp32 products and applies
          the scale ONCE on the final K tile — cheaper than scaling tiles.
  int4  : q (K//2, N) uint8, two values packed per byte along K (even row in
          the low nibble, odd in the high), scale (K//group, N) fp32 with
          ``group`` consecutive K rows per scale. Scales vary along K, so
          each tile is unpacked, sign-extended, and scaled before its MXU
          contraction. The wrapper splits x into its even and odd K columns,
          so the low and high nibbles contract as two plain matmuls and no
          row interleave happens in VMEM; a K tile spans a multiple of 8
          scale groups, so the scale block is (8k, Nt) as the TPU's (8, 128)
          tiling asks.

Grid is (M tiles, N tiles, K tiles) with the K axis minor/sequential so the
fp32 accumulator scratch carries across K tiles — the same convention as
flash_decode's kv-tile axis. The AWQ activation pre-scale is applied to x by
the ``ops.dequant_matmul`` wrapper (one VPU-sized elementwise multiply), not
here: it is a property of the activation, not the weight tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M_TILE = 128
N_TILE = 128
K_TILE = 256


def _int8_kernel(x_ref, q_ref, scale_ref, out_ref, acc_scr, *, n_k):
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)                   # (Mt, Kt)
    w = q_ref[...].astype(jnp.float32)                   # (Kt, Nt) int values
    acc_scr[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kidx == n_k - 1)
    def _done():
        out_ref[...] = (acc_scr[...] * scale_ref[0][None, :]).astype(out_ref.dtype)


def _unpack_int4(packed):
    """(Kt//2, Nt) uint8 -> low and high nibbles as (Kt//2, Nt) fp32 in
    [-8, 7] (even K rows = low nibble, odd = high)."""
    b = packed.astype(jnp.int32)
    lo = b & 0xF
    hi = (b >> 4) & 0xF
    lo = lo - 16 * (lo >= 8)
    hi = hi - 16 * (hi >= 8)
    return lo.astype(jnp.float32), hi.astype(jnp.float32)


def _int4_kernel(xe_ref, xo_ref, q_ref, scale_ref, out_ref, acc_scr, *, n_k,
                 group):
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo, hi = _unpack_int4(q_ref[...])                    # (Kt//2, Nt) each
    s = scale_ref[...]                                   # (Kt//group, Nt)
    ng, nt = s.shape
    half = group // 2                # packed rows per scale group

    def scaled(w):
        return (w.reshape(ng, half, nt) * s[:, None, :]).reshape(ng * half, nt)

    xe = xe_ref[...].astype(jnp.float32)                 # (Mt, Kt//2)
    xo = xo_ref[...].astype(jnp.float32)
    acc_scr[...] += (
        jnp.dot(xe, scaled(lo), preferred_element_type=jnp.float32)
        + jnp.dot(xo, scaled(hi), preferred_element_type=jnp.float32))

    @pl.when(kidx == n_k - 1)
    def _done():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def _pick_tile(dim: int, cap: int, multiple: int = 1) -> int:
    """Largest divisor of ``dim`` that is <= cap and a multiple of
    ``multiple`` (falls back to ``dim`` itself — one tile)."""
    t = min(cap, dim)
    t -= t % multiple
    while t >= multiple:
        if dim % t == 0:
            return t
        t -= multiple
    return dim


def quant_matmul(x, q, scale, *, bits: int, group: int = 0, interpret=True):
    """x (M, K) @ dequant(q, scale) -> (M, N) fp32.

    bits=8: q (K, N) int8, scale (1, N); bits=4: q (K//2, N) uint8 packed,
    scale (K//group, N) with ``group`` dividing K. M is padded up to the row
    tile; K/N tiles are chosen as aligned divisors.
    """
    M, K = x.shape
    N = q.shape[1]
    mt = min(M_TILE, M)
    if M % mt:
        pad = mt - M % mt
        out = quant_matmul(jnp.pad(x, ((0, pad), (0, 0))), q, scale,
                           bits=bits, group=group, interpret=interpret)
        return out[:M]
    nt = _pick_tile(N, N_TILE)
    if bits == 8:
        kt = _pick_tile(K, K_TILE)
        grid = (M // mt, N // nt, K // kt)
        kernel = functools.partial(_int8_kernel, n_k=grid[2])
        x_specs = [pl.BlockSpec((mt, kt), lambda m, n, k: (m, k))]
        xs = (x,)
        q_spec = pl.BlockSpec((kt, nt), lambda m, n, k: (k, n))
        s_spec = pl.BlockSpec((1, nt), lambda m, n, k: (0, n))
    elif bits == 4:
        g = group if group else K
        assert g % 2 == 0 and K % g == 0, (K, group)
        assert scale.shape[0] == K // g, (scale.shape, K, g)
        # a K tile holds a multiple of 8 scale groups (or all of K)
        kt = _pick_tile(K, max(K_TILE, 8 * g), 8 * g)
        grid = (M // mt, N // nt, K // kt)
        kernel = functools.partial(_int4_kernel, n_k=grid[2], group=g)
        x_specs = [pl.BlockSpec((mt, kt // 2), lambda m, n, k: (m, k))] * 2
        xs = (x[:, 0::2], x[:, 1::2])
        q_spec = pl.BlockSpec((kt // 2, nt), lambda m, n, k: (k, n))
        s_spec = pl.BlockSpec((kt // g, nt), lambda m, n, k: (k, n))
    else:
        raise ValueError(f"unsupported bits {bits}")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=x_specs + [q_spec, s_spec],
        out_specs=pl.BlockSpec((mt, nt), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((mt, nt), jnp.float32)],
        interpret=interpret,
    )(*xs, q, scale)
