"""Chip smoke test: the speculative-decoding server's main path on one TPU.

    python3 chip_smoke.py

Phases, in order; any failure exits non-zero:

  a. device: the first JAX device must be a TPU and the Pallas kernels must
     compile natively (``kernels.ops.INTERPRET`` False). Without a TPU the
     script stops here.
  b. serving: phi4-mini-3.8b at all 32 layers and its published widths, with
     its own drafter (4 layers, d_model 1024), bf16 weights drawn from a
     seed, built by ``launch.serve``'s own builders and served by the
     ``ContinuousEngine``: chain speculation, gamma 3, temperature 0, paged
     KV pool, 8 requests of 128-512 prompt tokens and 64 new tokens each,
     max_batch 8. The requests are served twice by fresh engines: the first
     pass compiles, the second is timed. Checks: every request returns
     exactly 64 ids in [0, vocab); tau >= 1; both passes return the same ids;
     the target's own full-sequence greedy pick agrees with the served ids.
  c. kernels: each Pallas kernel the serving path can dispatch (tree verify
     attention, int8/int4 dequant-matmul), compiled for the chip at the
     model's widths, against its oracle in ``kernels.ref``.
  d. the last stdout line is the JSON object
     ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Compiles are timed with JAX's compile events; the persistent compilation
cache is placed by ``launch.compile_cache``, so a second run in the same
checkout reports the warm compile time.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.recompile import CompileWatcher  # noqa: E402
from repro.core.metrics import latency_percentiles  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.quant_matmul import quant_matmul  # noqa: E402
from repro.kernels.tree_attention import tree_attention  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.quant import quantize_weight  # noqa: E402
from repro.spectree import TreeSpec  # noqa: E402

SERVE_ARGV = ["--arch", "phi4-mini-3.8b", "--continuous", "--gamma", "3",
              "--temperature", "0", "--requests", "8", "--max-new", "64",
              "--prompt-len", "256", "--mixed-lens", "--max-batch", "8"]
#: share of served ids the target's full-sequence greedy forward picks after
#: the same prefix. Served decode (paged, chunked, bf16) and the reference
#: forward (one causal pass) round differently, and the near-flat logits of
#: random weights leave bf16 near-ties that then flip (about 1 id in 20 on a
#: v5e); a broken KV, mask or position path agrees on almost nothing.
MIN_REFERENCE_AGREEMENT = 0.9


def memory() -> str:
    """Device memory now and the peak so far (the peak never falls, so the
    phase whose line first shows a peak raised it)."""
    st = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


def check_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's first device is "
                         f"{dev.platform!r}); nothing was run")
    if ops.INTERPRET:
        raise SystemExit("chip_smoke: Pallas kernels are in interpret mode "
                         "on a TPU (REPRO_PALLAS_INTERPRET set?)")
    return dev


def serve_pass(args, served):
    """Serve the requests once through a fresh engine. -> (results by id,
    per-request stats, wall seconds, compile seconds)."""
    engine, reqs, _ = serve.build_continuous(args, served,
                                             np.random.default_rng(0))
    with CompileWatcher() as clock:
        t0 = time.perf_counter()
        for r in reqs:
            engine.submit(r)
        results = engine.run()
        wall = time.perf_counter() - t0
    by_id = {r.request_id: r for r in results}
    stats = [engine.stats[i] for i in sorted(by_id)]
    prompts = {r.request_id: r.prompt for r in reqs}
    return by_id, stats, prompts, wall, clock.seconds


def reference_agreement(served, prompts, results, max_seq):
    """Share of served ids equal to the target's greedy pick from one
    full-sequence forward over prompt + served ids (one compile: every
    sequence is padded to ``max_seq``; padding sits after the causal
    positions that are read)."""
    target, params = served.target, served.t_params
    greedy = jax.jit(lambda p, t: jnp.argmax(target.logits(p, t)[0], -1))
    hits = total = 0
    for rid, res in results.items():
        prompt, out = np.asarray(prompts[rid]), np.asarray(res.tokens)
        seq = np.zeros((1, max_seq), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + len(out)] = out
        pick = np.asarray(greedy(params, jnp.asarray(seq)))[0]
        want = pick[len(prompt) - 1:len(prompt) - 1 + len(out)]
        hits += int(np.sum(want == out))
        total += len(out)
    return hits / total


def serving_phase(failures):
    args = serve.parse_args(SERVE_ARGV)
    with CompileWatcher() as init_clock:
        t0 = time.perf_counter()
        served = serve.build_models(args, np.random.default_rng(0))
        jax.block_until_ready((served.t_params, served.d_params))
        init_s = time.perf_counter() - t0
    cfg, dcfg = served.cfg, served.draft.cfg
    n_t = serve.count_params(served.t_params)
    n_d = serve.count_params(served.d_params)
    # matmul and embedding weights; RMS-norm scales stay float32 by design
    dtypes = {str(x.dtype) for path, x in jax.tree_util.tree_leaves_with_path(
        (served.t_params, served.d_params)) if "norm" not in str(path)}
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} params={n_t} weight_dtypes={sorted(dtypes)}")
    print(f"drafter: {dcfg.name} layers={dcfg.num_layers} "
          f"d_model={dcfg.d_model} params={n_d}")
    print(f"weights: built in {init_s:.2f}s "
          f"(compile {init_clock.seconds:.2f}s); {memory()}")
    if dtypes != {"bfloat16"}:
        failures.append(f"weights not all bf16: {sorted(dtypes)}")

    cold, _, prompts, cold_wall, cold_compile = serve_pass(args, served)
    warm, stats, _, wall, warm_compile = serve_pass(args, served)
    tokens = sum(len(r.tokens) for r in warm.values())
    tau = float(np.mean([s.sd.tau for s in stats]))
    ttft = latency_percentiles([s.ttft_s for s in stats])
    tpot = latency_percentiles([s.tpot_s for s in stats])
    print(f"serving: {len(warm)} requests, {tokens} tokens, "
          f"prompt lens {sorted(len(p) for p in prompts.values())}")
    print(f"serving: cold pass {cold_wall:.3f}s (compile "
          f"{cold_compile:.3f}s); warm pass {wall:.3f}s (compile "
          f"{warm_compile:.3f}s) -> {tokens / wall:.2f} tok/s")
    print(f"serving: tau={tau:.4f} TTFT p50={ttft['p50_ms']:.2f}ms "
          f"p99={ttft['p99_ms']:.2f}ms TPOT p50={tpot['p50_ms']:.3f}ms "
          f"p99={tpot['p99_ms']:.3f}ms; {memory()}")

    if sorted(warm) != list(range(args.requests)):
        failures.append(f"served request ids {sorted(warm)}")
    for rid, r in warm.items():
        ids = np.asarray(r.tokens)
        if ids.shape != (args.max_new,):
            failures.append(f"request {rid}: {ids.shape[0]} ids, "
                            f"want {args.max_new}")
        elif ids.min() < 0 or ids.max() >= cfg.vocab_size:
            failures.append(f"request {rid}: ids outside [0, vocab)")
        if not np.array_equal(ids, np.asarray(cold[rid].tokens)):
            failures.append(f"request {rid}: cold and warm passes differ")
    if not tau >= 1.0:
        failures.append(f"tau {tau} < 1")

    max_seq = max(len(p) for p in prompts.values()) + args.max_new
    with CompileWatcher() as ref_clock:
        agree = reference_agreement(served, prompts, warm, max_seq)
    print(f"serving: reference greedy agreement {agree:.4f} "
          f"(min {MIN_REFERENCE_AGREEMENT}; compile {ref_clock.seconds:.2f}s); "
          f"{memory()}")
    if not agree >= MIN_REFERENCE_AGREEMENT:
        failures.append(f"reference agreement {agree:.4f}")
    return cfg


def _check(name, got, want, atol, why, failures):
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    ok = bool(jnp.all(jnp.isfinite(got))) and err <= atol
    print(f"kernel {name}: max|err|={err:.3e} atol={atol:.3e} "
          f"{'ok' if ok else 'FAIL'} ({why})")
    if not ok:
        failures.append(f"kernel {name}: max|err| {err:.3e} > {atol:.3e}")


def kernel_phase(cfg, failures):
    """Kernels compiled for the chip (interpret=False) vs their oracles,
    which run at HIGHEST matmul precision so the tolerance is the kernel's
    own error."""
    rng = np.random.default_rng(0)

    # tree verify: a (2, 2) tree over a 1024-slot window at the model's
    # GQA shape (Hkv 8, G 3, hd 128), 8 rows
    spec = TreeSpec((2, 2))
    B, S, Hkv, hd = 8, 1024, cfg.num_kv_heads, cfg.head_dim_
    G, N = cfg.num_heads // cfg.num_kv_heads, spec.num_nodes
    q = jnp.asarray(rng.normal(size=(B, Hkv, N, G, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.bfloat16)
    lengths = rng.integers(S // 2, S - N, B)
    mask = np.zeros((B, N, S), bool)
    for b, L in enumerate(lengths):
        mask[b, :, :L] = True                     # committed prefix
        mask[b, :, L:L + N] = spec.ancestors()    # root path, self inclusive
    mask = jnp.asarray(mask)
    got = jax.jit(lambda *a: tree_attention(*a, interpret=False))(q, k, v,
                                                                   mask)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.ref_tree_attention)(q, k, v, mask)
    _check(f"tree_attention B={B} Hkv={Hkv} N={N} G={G} hd={hd} S={S}",
           got, want, 2e-2,
           "probabilities may enter the MXU as bf16 (2^-9 relative); "
           "|v| <= 5 bounds that by 1e-2", failures)

    # dequant-matmul at the FFN's in-projection, 8 decode rows
    M, K, Nn = 8, cfg.d_model, cfg.d_ff
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w = rng.normal(size=(K, Nn)).astype(np.float32) / math.sqrt(K)
    for bits, group, atol_rel, why in (
            (8, 0, 1e-4, "int8 and bf16 operands are exact on the MXU; "
                         "only f32 accumulation order differs"),
            (4, 64, 1e-2, "scaled int4 weights may enter the MXU as bf16 "
                          "(2^-9 relative per product)")):
        qw = quantize_weight(w, bits=bits, group=group)
        got = jax.jit(lambda a, b, c, bits=bits, group=group: quant_matmul(
            a, b, c, bits=bits, group=group, interpret=False))(
                x, qw.q, qw.scale)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda a, b, c, bits=bits, group=group:
                           ref.ref_quant_matmul(a, b, c, bits, group))(
                x, qw.q, qw.scale)
        atol = atol_rel * float(jnp.max(jnp.abs(want)))
        _check(f"quant_matmul int{bits} M={M} K={K} N={Nn}"
               + (f" group={group}" if group else ""),
               got, want, atol, why + f"; atol = {atol_rel} x max|want|",
               failures)


def main():
    dev = check_device()
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    failures = []
    t0 = time.perf_counter()
    with CompileWatcher() as clock:
        cfg = serving_phase(failures)
        kernel_phase(cfg, failures)
    print(f"memory: {memory()}")
    print(f"compile: {clock.seconds:.3f}s in {clock.n_compiles} compiles; "
          f"total {time.perf_counter() - t0:.3f}s")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
