"""Pallas kernel lint: static BlockSpec/grid validation across swept shapes.

The repo's kernels (quant_matmul, flash_decode, tree_attention,
paged_attention, the distill loss trio) run in interpret mode in CI —
nothing there exercises the TPU resource constraints they were tiled for.
This linter recovers the *actual* ``pl.pallas_call`` invocation each
wrapper would make for a given problem shape — by monkeypatching
``pallas_call`` and tracing the wrapper under ``jax.eval_shape``, so the
real tiling code runs but no kernel executes —
and then validates, per (kernel, shape):

  KN001  VMEM footprint: every pipelined in/out block is double-buffered
         (x2) and scratch is resident once; the total must fit the per-core
         VMEM budget (~16 MiB, pallas_guide.md). Failures name the kernel,
         the shape, and the byte overage.
  KN002  divisibility: each block dim must divide its operand dim (a
         non-dividing block silently reads OOB-padded garbage or faults at
         Mosaic compile time on hardware).
  KN003  dtype rules: floating accumulator / reduction scratch must be
         float32 (bf16 accumulation loses the low mantissa bits the loss
         kernels depend on; the MXU accumulates in f32 anyway).
  KN004  lane alignment (warning): a last-dim block size over one lane
         width that is not a multiple of 128 wastes lanes on every access.

Shapes are swept from the repo's model configs (tiny CI shapes up to
7B-class serving shapes) — abstract tracing makes the 7B cases free.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .findings import ERROR, WARN, Finding, FindingSet

VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # per-core VMEM (pallas_guide.md)
DOUBLE_BUFFER = 2                      # pipelined in/out blocks are 2x
LANE = 128

# minimum (sublane, lane) tile per dtype — pallas_guide.md
MIN_TILE = {1: (32, 128), 2: (16, 128), 4: (8, 128)}


@dataclass
class PallasCallRecord:
    """One captured ``pl.pallas_call`` invocation (never executed)."""

    kernel_name: str
    grid: Tuple[int, ...]
    in_blocks: List[Tuple[Tuple[int, ...], str]]    # (block_shape, dtype)
    out_blocks: List[Tuple[Tuple[int, ...], str]]
    scratch: List[Tuple[Tuple[int, ...], str]]
    operand_shapes: List[Tuple[int, ...]]
    out_shapes: List[Tuple[int, ...]]
    # scratch of an HBM operand's dtype: what the kernel's DMAs copy into
    dma_buffers: List[Tuple[Tuple[int, ...], str]] = field(
        default_factory=list)

    def block_bytes(self) -> int:
        per_step = 0
        for shape, dtype in self.in_blocks + self.out_blocks:
            per_step += _nbytes(shape, dtype) * DOUBLE_BUFFER
        for shape, dtype in self.scratch + self.dma_buffers:
            per_step += _nbytes(shape, dtype)
        return per_step


def _nbytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= max(int(d), 1)
    return n * jnp.dtype(dtype).itemsize


def _kernel_name(fn) -> str:
    while isinstance(fn, functools.partial):
        fn = fn.func
    return getattr(fn, "__name__", repr(fn))


def _is_buffer(scratch) -> bool:
    """A scratch entry that holds data (not a DMA or sync semaphore)."""
    try:
        jnp.dtype(scratch.dtype)
    except TypeError:
        return False
    return True


def _block_shape(spec, operand_shape) -> Tuple[int, ...]:
    """Resolve a BlockSpec's block shape against its operand (None entries
    mean a squeezed size-1 dim; a missing block_shape means whole-array)."""
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return tuple(operand_shape)
    return tuple(1 if b is None else int(b) for b in bs)


def capture_pallas_calls(fn: Callable, *abstract_args,
                         **kw) -> List[PallasCallRecord]:
    """Trace ``fn`` under ``jax.eval_shape`` with ``pl.pallas_call``
    replaced by a recorder: the wrapper's real tiling logic runs (tile
    picking, padding, grid math), the kernel body never does."""
    records: List[PallasCallRecord] = []
    real = pl.pallas_call

    def recorder(kernel, *, grid=(), in_specs=None, out_specs=None,
                 out_shape=None, scratch_shapes=(), grid_spec=None,
                 **unused):
        n_prefetch = 0
        if grid_spec is not None:   # scalar-prefetch grid (SMEM operands)
            grid, in_specs = grid_spec.grid, grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = grid_spec.scratch_shapes
            n_prefetch = grid_spec.num_scalar_prefetch

        def fake_call(*operands):
            out_list, out_def = jax.tree_util.tree_flatten(out_shape)
            specs = (out_specs if isinstance(out_specs, (list, tuple))
                     else [out_specs])
            # operands left in HBM (memory_space ANY, copied in by the
            # kernel's own DMAs) take no pipelined VMEM block
            pairs = list(zip(in_specs or [], operands[n_prefetch:]))
            in_hbm = {str(o.dtype) for s, o in pairs
                      if getattr(s, "memory_space", None) is pl.ANY}
            blocked = [(s, o) for s, o in pairs
                       if getattr(s, "memory_space", None) is not pl.ANY]
            buffers = [(tuple(int(d) for d in s.shape),
                        str(jnp.dtype(s.dtype)))
                       for s in scratch_shapes if _is_buffer(s)]
            records.append(PallasCallRecord(
                kernel_name=_kernel_name(kernel),
                grid=tuple(int(g) for g in (grid if isinstance(
                    grid, (list, tuple)) else (grid,))),
                in_blocks=[(_block_shape(s, o.shape), str(o.dtype))
                           for s, o in blocked],
                out_blocks=[(_block_shape(s, o.shape), str(o.dtype))
                            for s, o in zip(specs, out_list)],
                scratch=[b for b in buffers if b[1] not in in_hbm],
                dma_buffers=[b for b in buffers if b[1] in in_hbm],
                operand_shapes=[tuple(o.shape) for _, o in blocked],
                out_shapes=[tuple(o.shape) for o in out_list],
            ))
            zeros = [jnp.zeros(o.shape, o.dtype) for o in out_list]
            return jax.tree_util.tree_unflatten(out_def, zeros)

        return fake_call

    pl.pallas_call = recorder
    try:
        jax.eval_shape(functools.partial(fn, **kw), *abstract_args)
    finally:
        pl.pallas_call = real
    return records


# ------------------------------------------------------------------ rules

def lint_record(rec: PallasCallRecord, case: str,
                budget: int = VMEM_BUDGET_BYTES) -> List[Finding]:
    findings = []
    total = rec.block_bytes()
    if total > budget:
        findings.append(Finding(
            checker="kernel", rule="KN001",
            location=f"{rec.kernel_name}[{case}]",
            message=f"VMEM footprint {total} B exceeds the {budget} B "
                    f"per-core budget by {total - budget} B "
                    f"(grid={rec.grid}, blocks x{DOUBLE_BUFFER} + scratch)",
            data={"kernel": rec.kernel_name, "case": case, "bytes": total,
                  "budget": budget, "over": total - budget,
                  "grid": list(rec.grid)}))
    all_blocks = list(zip(rec.in_blocks, rec.operand_shapes)) + \
        list(zip(rec.out_blocks, rec.out_shapes))
    for (block, dtype), full in all_blocks:
        if len(block) != len(full):
            continue   # squeezed specs; divisibility judged dim-wise below
        for b, d in zip(block, full):
            if b > 0 and d % b:
                findings.append(Finding(
                    checker="kernel", rule="KN002",
                    location=f"{rec.kernel_name}[{case}]",
                    message=f"block dim {b} does not divide operand dim {d} "
                            f"(block {block} vs array {full}) — partial "
                            f"tiles read past the array on hardware",
                    data={"kernel": rec.kernel_name, "case": case,
                          "block": list(block), "array": list(full)}))
    for shape, dtype in rec.scratch:
        dt = jnp.dtype(dtype)
        # NB: ml_dtypes (bfloat16) report numpy kind 'V', not 'f' — test
        # via issubdtype so the rule's main target is actually in scope
        if jnp.issubdtype(dt, jnp.floating) and dt.itemsize < 4:
            findings.append(Finding(
                checker="kernel", rule="KN003",
                location=f"{rec.kernel_name}[{case}]",
                message=f"floating scratch accumulator is {dtype}; "
                        f"reductions must accumulate in float32",
                data={"kernel": rec.kernel_name, "case": case,
                      "scratch_dtype": str(dtype)}))
    for (block, dtype), full in all_blocks:
        if block and block[-1] > LANE and block[-1] % LANE:
            findings.append(Finding(
                checker="kernel", rule="KN004", severity=WARN,
                location=f"{rec.kernel_name}[{case}]",
                message=f"last block dim {block[-1]} exceeds one lane width "
                        f"but is not a multiple of {LANE} — partial lanes "
                        f"on every access",
                data={"kernel": rec.kernel_name, "case": case,
                      "block": list(block)}))
    return findings


# ------------------------------------------------------------------ sweep

@dataclass
class KernelCase:
    """One (kernel wrapper, abstract shapes) lint case."""

    name: str
    fn: Callable
    args: Tuple
    kwargs: Dict = field(default_factory=dict)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def build_kernel_cases() -> List[KernelCase]:
    """Sweep each kernel over CI-scale and serving-scale shapes.

    Serving-scale rows use 7B-class dims (d_model 4096, 32 heads, head_dim
    128, vocab 32000, ff 11008) — the shapes ROADMAP item 1 will actually
    run. All cases are abstract; nothing allocates.
    """
    from .jaxpr_audit import _tiny_models   # tiny config source of truth
    from ..kernels.quant_matmul import quant_matmul
    from ..kernels.flash_decode import flash_decode
    from ..kernels.tree_attention import tree_attention
    from ..kernels.paged_attention import paged_attention
    from ..kernels.distill_loss import loss_grad, loss_terms, row_logsumexp

    t, _ = _tiny_models()
    cfg = t.cfg
    hd_small, hd_big = cfg.d_model // cfg.num_heads, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    cases: List[KernelCase] = []

    # quant_matmul: (M, K, N) — tiny ff, 7B attention proj, 7B ff up-proj
    for tag, (M, K, N) in [("tiny", (8, cfg.d_model, cfg.d_ff)),
                           ("7b_qkv", (16, 4096, 4096)),
                           ("7b_ffn", (16, 4096, 11008))]:
        for bits in (8, 4):
            group = 0 if bits == 8 else (128 if K >= 128 else 16)
            qshape = (K // 2, N) if bits == 4 else (K, N)
            sshape = (K // group, N) if bits == 4 else (1, N)
            qdt = jnp.uint8 if bits == 4 else jnp.int8
            cases.append(KernelCase(
                name=f"quant_matmul_int{bits}_{tag}",
                fn=quant_matmul,
                args=(_sds((M, K), f32), _sds(qshape, qdt),
                      _sds(sshape, f32)),
                kwargs={"bits": bits, "group": group}))

    # flash_decode: (B, Hkv, G, hd) vs (B, S, Hkv, hd)
    for tag, (B, Hkv, G, hd, S) in [
            ("tiny", (4, cfg.num_kv_heads,
                      cfg.num_heads // cfg.num_kv_heads, hd_small, 256)),
            ("7b_gqa", (8, 8, 4, hd_big, 4096))]:
        cases.append(KernelCase(
            name=f"flash_decode_{tag}", fn=flash_decode,
            args=(_sds((B, Hkv, G, hd), f32), _sds((B, S, Hkv, hd), bf16),
                  _sds((B, S, Hkv, hd), bf16), _sds((B, S), jnp.bool_))))

    # tree_attention: N tree nodes per row
    for tag, (B, Hkv, N, G, hd, S) in [
            ("tiny", (4, cfg.num_kv_heads, 7,
                      cfg.num_heads // cfg.num_kv_heads, hd_small, 256)),
            ("7b_gqa", (8, 8, 15, 4, hd_big, 4096))]:
        cases.append(KernelCase(
            name=f"tree_attention_{tag}", fn=tree_attention,
            args=(_sds((B, Hkv, N, G, hd), f32),
                  _sds((B, S, Hkv, hd), bf16), _sds((B, S, Hkv, hd), bf16),
                  _sds((B, N, S), jnp.bool_))))

    # paged_attention: stacked (L, P, page, Hkv, hd) pools read in place
    for tag, (B, T, H, Hkv, hd, L, P, mp, dt) in [
            ("tiny", (4, 4, cfg.num_heads, cfg.num_kv_heads, hd_small, 2,
                      64, 8, f32)),
            ("phi4_verify", (32, 4, 24, 8, hd_big, 32, 1200, 257, bf16)),
            ("phi4_prefill", (1, 256, 24, 8, hd_big, 32, 1200, 257, bf16)),
            ("phi4_drafter", (32, 1, 8, 4, hd_big, 4, 1200, 257, bf16)),
            # one chip's share of yi9b-chat's pools split by head over four
            ("one_head_verify", (32, 4, 8, 1, hd_big, 48, 4096, 257, bf16)),
            ("one_head_prefill", (1, 256, 8, 1, hd_big, 48, 4096, 257, bf16)),
            ("one_head_drafter", (32, 1, 2, 1, hd_big, 4, 4096, 257, bf16))]:
        cases.append(KernelCase(
            name=f"paged_attention_{tag}", fn=paged_attention,
            args=(_sds((B, T, H, hd), dt), _sds((L, P, 16, Hkv, hd), dt),
                  _sds((L, P, 16, Hkv, hd), dt),
                  _sds((L, P, 16), jnp.int32), _sds((B, mp), jnp.int32),
                  _sds((B, T), jnp.int32))))

    # distill loss trio: (rows, vocab)
    for tag, (R, V) in [("tiny", (64, cfg.vocab_size)),
                        ("7b_vocab", (256, 32000))]:
        s, t_ = _sds((R, V), f32), _sds((R, V), f32)
        lse = _sds((R,), f32)
        scalar = _sds((), f32)
        cases.append(KernelCase(name=f"row_logsumexp_{tag}",
                                fn=row_logsumexp, args=(s,)))
        cases.append(KernelCase(
            name=f"loss_terms_{tag}", fn=loss_terms,
            args=(s, t_, lse, lse, scalar, scalar),
            kwargs={"mode": "tvdpp"}))
        cases.append(KernelCase(
            name=f"loss_grad_{tag}", fn=loss_grad,
            args=(s, t_, lse, lse, lse, lse, scalar, scalar),
            kwargs={"mode": "tvdpp"}))
    return cases


def run_kernel_lint(cases: Optional[Sequence[KernelCase]] = None,
                    budget: int = VMEM_BUDGET_BYTES) -> FindingSet:
    """Capture + lint every case; a wrapper that fails to trace at a swept
    shape is itself a finding (the shape contract is part of the API)."""
    if cases is None:
        cases = build_kernel_cases()
    fs = FindingSet()
    n_calls = 0
    for case in cases:
        try:
            records = capture_pallas_calls(case.fn, *case.args, **case.kwargs)
        except Exception as e:   # noqa: BLE001 - any trace failure is a finding
            fs.add(Finding(
                checker="kernel", rule="KN002", location=case.name,
                message=f"kernel wrapper failed to trace at swept shape: "
                        f"{type(e).__name__}: {str(e)[:200]}",
                data={"case": case.name, "error": str(e)}))
            continue
        if not records:
            fs.add(Finding(
                checker="kernel", rule="KN002", severity=WARN,
                location=case.name,
                message="no pallas_call observed (wrapper bypassed the "
                        "kernel at this shape)",
                data={"case": case.name}))
        for rec in records:
            n_calls += 1
            fs.extend(lint_record(rec, case.name, budget=budget))
    fs.stats = {"cases": len(cases),    # type: ignore[attr-defined]
                "pallas_calls": n_calls, "budget_bytes": budget}
    return fs
