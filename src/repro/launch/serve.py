"""Serving launcher: speculative decoding with the arch's drafter.

Static batching (default):

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --gamma 3 --requests 8 --max-new 32

Continuous batching (paged KV pool + scheduler + streaming engine), with a
Poisson arrival process and optionally mixed prompt lengths:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --continuous --requests 16 --arrival-rate 4 --mixed-lens

Tree-structured speculation (repro.spectree): verify a token tree per round
instead of a chain — ``--tree-depth d --tree-branch k`` builds a uniform
(k,)*d tree. Works standalone (batched generate) and with --continuous:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --tree --tree-depth 2 --tree-branch 3 [--continuous]

Quantized decode (repro.quant): ``--quant-weights {int8,int4}`` post-
training-quantizes the drafter (AWQ-lite calibrated on datagen batches from
the target; add ``--quant-target`` to quantize the target too) and
``--quant-kv`` switches both KV caches/pools to int8 with per-slot scales:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --quant-weights int8 --quant-kv [--continuous] [--tree]

Self-speculative draft heads (repro.draftheads) instead of a separate
drafter model: ``--draft-head {eagle,medusa}`` drafts from the target's own
hidden states — no second model, no drafter KV cache/pages. Composes with
--continuous and --tree; ``--head-ckpt`` loads heads trained by
``launch.train --draft-head`` (without it the heads are randomly
initialized — correct at any temperature by rejection sampling, just with
lower acceptance; Medusa's near-zero warm start already tracks the target):

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --draft-head eagle [--head-ckpt heads.npz] [--continuous] [--tree]

Prefix sharing + multi-tenant traffic (repro.serving.prefix_cache /
repro.traffic): ``--prefix-cache`` turns on the copy-on-write radix cache
over the paged KV pool (shared prompt prefixes prefill once; temp-0
token-identical), ``--traffic-mix`` replays a scenario mix (shared-prefix
chat / long-context summarize / bursty short queries) instead of random
prompts, and ``--aging-s`` bounds priority-queue starvation:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --continuous --prefix-cache --traffic-mix chat --requests 16 \
      --arrival-rate 8 [--policy priority --aging-s 0.5]

Observability (repro.obs, all opt-in): ``--trace-out trace.json`` writes a
Chrome/Perfetto trace of the run (per-request lifecycle tracks + engine
spans — load at https://ui.perfetto.dev), ``--metrics-out m.jsonl`` appends
periodic metrics-registry snapshots (final Prometheus exposition to
``m.jsonl.prom``), and ``--jax-profile DIR`` captures a jax.profiler
device trace of the serve loop, whose ops carry the named scopes draft /
verify / commit, attention / kv_write / kv_gather / sdpa, mlp, lm_head and
sampling — the per-phase split of a round (with ``--trace-out`` too, the
engine's spans land on its host plane):

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --continuous --requests 8 --trace-out trace.json \
      --metrics-out metrics.jsonl --jax-profile prof

Speculation-quality telemetry (repro.obs.quality): ``--quality-telemetry``
makes the jitted round leave per-depth TVD/entropy/accept buffers in the
round state (fetched with the round's existing device_get — temp-0 token-
identical) and prints per-depth acceptance/TVD, the acceptance-vs-entropy
curve, drafter-drift alarms (Page–Hinkley on the round acceptance
fraction), and the measured-vs-i.i.d. acceptance attribution report.
``--flight-record [DIR]`` keeps a bounded ring of per-round records dumped
as post-mortem JSON on drift alarm / SLO breach / crash; ``--slo-ttft-ms``
+ ``--slo-tpot-ms`` arm multi-window burn-rate SLO tracking over request
latencies:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --continuous --requests 16 --quality-telemetry --flight-record \
      --slo-ttft-ms 500 --slo-tpot-ms 50
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

from ..configs import ARCHS, QuantConfig, get_config, reduced
from ..configs.base import ModelConfig
from ..core.datagen import DatagenConfig, generate_distillation_dataset
from ..core.metrics import SDStats, latency_percentiles, mbsu
from ..core.speculative import SDConfig
from ..draftheads import HeadConfig, HeadDrafter
from ..models.model import Model
from ..obs import (MetricsRegistry, SLOConfig, Tracer, acceptance_report,
                   format_acceptance_report, jax_profile)
from ..quant import quantize_params
from ..serving import ContinuousEngine, Request, ServeRequest, ServingEngine
from ..spectree import TreeSpec, tree_speculative_generate
from .compile_cache import enable_compile_cache


def count_params(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


def init_weights(model: Model, key):
    """Random weights built inside one jit: each float32 draw fuses with its
    cast to the param dtype, so no float32 copy of a stacked layer tensor is
    ever materialized next to the weights."""
    return jax.jit(lambda k: model.init(k)[0])(key)


@dataclass
class Served:
    """Target and drafter as the launcher serves them."""
    cfg: ModelConfig
    target: Model
    t_params: Any
    draft: Any                  # Model, or HeadDrafter with --draft-head
    d_params: Any
    draft_name: str
    c: float                    # drafter/target parameter ratio (MBSU)


def build_models(args, rng) -> Served:
    """Target + drafter for ``args`` (see ``parse_args``). Served weights are
    held in the compute dtype (bf16); training keeps float32 masters.
    ``rng`` draws the AWQ calibration seeds under --quant-weights."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.num_codebooks > 1:
        # The SD engine streams one token id per step; multi-codebook audio
        # decodes K ids per step (flattened-sum interleave, DESIGN.md §4).
        # The demo launcher serves the single-codebook variant; the full
        # K-codebook decode path is exercised by dryrun + test_serving_system.
        print(f"note: serving single-codebook variant of {cfg.name}")
        cfg = cfg.replace(num_codebooks=1)
    cfg = cfg.replace(param_dtype=cfg.dtype)
    target = Model(cfg)
    t_params = init_weights(target, jax.random.PRNGKey(0))
    if args.draft_head is not None:
        if args.no_draft:
            raise SystemExit("--draft-head and --no-draft are exclusive")
        if args.quant_weights is not None:
            raise SystemExit("--quant-weights applies to the separate "
                             "drafter model; not supported with --draft-head")
        draft = HeadDrafter(HeadConfig.for_target(
            args.draft_head, cfg, num_medusa_heads=args.medusa_heads))
        if args.head_ckpt:
            from ..checkpoint import load_draft_heads
            d_params = load_draft_heads(args.head_ckpt, draft)
        else:
            d_params = draft.init(jax.random.PRNGKey(1))
        draft_name = f"{args.draft_head}-head"
        n_draft = draft.hc.param_count()
    else:
        d_cfg = cfg.drafter().replace(vocab_size=cfg.vocab_size,
                                      param_dtype=cfg.dtype)
        draft = Model(d_cfg)
        d_params = init_weights(draft, jax.random.PRNGKey(1))
        draft_name, n_draft = d_cfg.name, count_params(d_params)
    c = n_draft / count_params(t_params)
    print(f"arch={cfg.name} draft={draft_name} c={c:.4f}")

    if args.quant_weights is not None:
        if args.no_draft:
            raise SystemExit("--quant-weights applies to the drafter")
        qcfg = QuantConfig(weights=args.quant_weights,
                           group_size=args.quant_group)
        # AWQ calibration batches from the distillation datagen pipeline:
        # target-generated responses are the drafter's serving distribution
        seeds = rng.integers(3, cfg.vocab_size,
                             (args.calib_seqs, args.prompt_len)).astype(np.int32)
        calib = generate_distillation_dataset(
            target, t_params, seeds,
            DatagenConfig(temperatures=(0.0, 0.7), max_response_tokens=16,
                          batch_size=args.calib_seqs))
        d_params = quantize_params(draft, d_params, qcfg, calib_tokens=calib)
        if args.quant_target:
            t_params = quantize_params(target, t_params, qcfg,
                                       calib_tokens=calib)
        print(f"quantized weights={args.quant_weights} "
              f"target={'yes' if args.quant_target else 'no'} "
              f"kv={'int8' if args.quant_kv else 'fp'}")
    return Served(cfg, target, t_params, draft, d_params, draft_name, c)


def prompt_lengths(args, rng) -> np.ndarray:
    if args.mixed_lens:
        return rng.integers(max(args.prompt_len // 2, 1),
                            2 * args.prompt_len + 1, args.requests)
    return np.full(args.requests, args.prompt_len)


def build_continuous(args, served: Served, rng, tracer=None, registry=None):
    """The continuous engine for ``args`` and its requests, not yet
    submitted. Returns (engine, requests)."""
    cfg, draft = served.cfg, served.draft
    if args.traffic_mix is not None:
        from ..traffic import make_mix
        serve_reqs = make_mix(args.traffic_mix).build(
            args.requests, args.arrival_rate, cfg.vocab_size, seed=0)
        max_seq = max(len(r.prompt) + r.max_new_tokens for r in serve_reqs)
    else:
        lens = prompt_lengths(args, rng)
        arrivals = (np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                              args.requests))
                    if args.arrival_rate > 0 else np.zeros(args.requests))
        serve_reqs = [ServeRequest(
            prompt=rng.integers(3, cfg.vocab_size, lens[i]).astype(np.int32),
            max_new_tokens=args.max_new, request_id=i,
            arrival_time_s=float(arrivals[i]))
            for i in range(args.requests)]
        max_seq = int(lens.max()) + args.max_new
    head = isinstance(draft, HeadDrafter)
    engine = ContinuousEngine(
        target=served.target, target_params=served.t_params,
        draft=None if head else draft,
        draft_params=None if head else served.d_params,
        draft_heads=draft if head else None,
        draft_head_params=served.d_params if head else None,
        sd=SDConfig(gamma=args.gamma, temperature=args.temperature,
                    kv_quant=args.quant_kv),
        tree=tree_spec(args),
        max_batch=args.max_batch, max_seq_len=max_seq,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
        policy=args.policy, aging_s=args.aging_s,
        kv_quant=args.quant_kv, prefix_cache=args.prefix_cache,
        tracer=tracer, registry=registry,
        metrics_out=args.metrics_out,
        quality=args.quality_telemetry,
        flight_record=args.flight_record is not None,
        flight_dir=args.flight_record or "flight",
        slo=(SLOConfig(ttft_ms=args.slo_ttft_ms, tpot_ms=args.slo_tpot_ms)
             if args.slo_ttft_ms is not None else None))
    return engine, serve_reqs


def tree_spec(args) -> Optional[TreeSpec]:
    return (TreeSpec((args.tree_branch,) * args.tree_depth)
            if args.tree else None)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--no-draft", action="store_true", help="AR baseline")
    ap.add_argument("--draft-head", choices=("eagle", "medusa"), default=None,
                    help="self-speculative draft heads in place of the "
                         "separate drafter model (repro.draftheads)")
    ap.add_argument("--medusa-heads", type=int, default=4,
                    help="number of parallel Medusa heads (offsets +1..+K)")
    ap.add_argument("--head-ckpt", default=None,
                    help="head checkpoint from launch.train --draft-head")
    ap.add_argument("--tree", action="store_true",
                    help="tree-structured speculation (repro.spectree)")
    ap.add_argument("--tree-depth", type=int, default=2,
                    help="tree levels below the root (chain-gamma analogue)")
    ap.add_argument("--tree-branch", type=int, default=2,
                    help="children per node at every level")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine (paged KV + scheduler)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals, requests/sec (0 = all at t=0)")
    ap.add_argument("--mixed-lens", action="store_true",
                    help="sample prompt lengths in [prompt_len/2, 2*prompt_len]")
    ap.add_argument("--quant-weights", choices=("int8", "int4"), default=None,
                    help="PTQ the drafter weights (AWQ-lite calibrated)")
    ap.add_argument("--quant-target", action="store_true",
                    help="also quantize the target's weights")
    ap.add_argument("--quant-kv", action="store_true",
                    help="int8 KV caches/pools with per-slot scales")
    ap.add_argument("--quant-group", type=int, default=64,
                    help="int4 scale group size (input channels)")
    ap.add_argument("--calib-seqs", type=int, default=4,
                    help="datagen seed sequences for AWQ calibration")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--policy", choices=("fcfs", "priority"), default="fcfs")
    ap.add_argument("--aging-s", type=float, default=None,
                    help="priority aging: a queued request gains one priority "
                         "class per this many seconds waited (no starvation)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged KV pool: shared "
                         "prompt prefixes prefill once (COW-safe, temp-0 "
                         "token-identical)")
    ap.add_argument("--traffic-mix", choices=("chat", "summarize", "bursty",
                                              "mixed"), default=None,
                    help="replay a repro.traffic scenario mix instead of "
                         "random prompts (continuous only)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "run (per-request lifecycle + engine spans)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append periodic metrics-registry JSONL snapshots; "
                         "final Prometheus exposition goes to PATH.prom")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the serve "
                         "loop into DIR (TensorBoard/Perfetto viewable)")
    ap.add_argument("--quality-telemetry", action="store_true",
                    help="per-depth TVD/entropy/acceptance analytics + "
                         "drafter-drift detection (temp-0 token-identical; "
                         "rides the round's existing device transfer)")
    ap.add_argument("--flight-record", nargs="?", const="flight",
                    default=None, metavar="DIR",
                    help="bounded per-round flight recorder; dumps a JSON "
                         "post-mortem bundle into DIR (default ./flight) on "
                         "drift alarm, SLO breach, or crash")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT SLO threshold; arms multi-window burn-rate "
                         "alerting (needs --slo-tpot-ms too)")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="TPOT SLO threshold; arms multi-window burn-rate "
                         "alerting (needs --slo-ttft-ms too)")
    args = ap.parse_args(argv)
    if args.quant_target and args.quant_weights is None:
        ap.error("--quant-target requires --quant-weights {int8,int4}")
    if args.traffic_mix is not None and not args.continuous:
        ap.error("--traffic-mix requires --continuous")
    for flag, val in (("--trace-out", args.trace_out),
                      ("--metrics-out", args.metrics_out),
                      ("--jax-profile", args.jax_profile),
                      ("--quality-telemetry", args.quality_telemetry),
                      ("--flight-record", args.flight_record),
                      ("--slo-ttft-ms", args.slo_ttft_ms),
                      ("--slo-tpot-ms", args.slo_tpot_ms)):
        if val and not args.continuous:
            ap.error(f"{flag} instruments the continuous engine; add "
                     "--continuous")
    if (args.slo_ttft_ms is None) != (args.slo_tpot_ms is None):
        ap.error("--slo-ttft-ms and --slo-tpot-ms come as a pair (burn "
                 "rates are tracked per metric over the same windows)")
    return args


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    rng = np.random.default_rng(0)
    served = build_models(args, rng)
    cfg, target, t_params = served.cfg, served.target, served.t_params
    draft, d_params, c = served.draft, served.d_params, served.c
    sdc = SDConfig(gamma=args.gamma, temperature=args.temperature,
                   kv_quant=args.quant_kv)

    tree = tree_spec(args)
    if tree is not None:
        if args.no_draft:
            raise SystemExit("--tree is speculative-only")
        print(f"tree: branching={tree.branching} nodes={tree.num_nodes} "
              f"(chain-equivalent gamma={tree.num_draft_nodes})")

    if tree is not None and not args.continuous:
        # batched tree generation (equal prompt lengths: one jitted round)
        prompt = jax.random.randint(jax.random.PRNGKey(3),
                                    (args.requests, args.prompt_len),
                                    3, cfg.vocab_size)
        toks, stats = tree_speculative_generate(
            draft, target, d_params, t_params, prompt, args.max_new, sdc, tree)
        # MBSU's draft-cost term counts *sequential* draft passes: a tree
        # round runs depth+1 batched level passes (chain analogue: gamma)
        print(f"tree SD: tau={stats.tau:.3f} "
              f"MBSU={mbsu(stats.tau, c, tree.depth):.3f} "
              f"{stats.tokens_per_s():.1f} tok/s")
        depth_acc = ", ".join(f"d{d}={r:.2f}"
                              for d, r in stats.depth_acceptance().items())
        print(f"  per-depth acceptance: {depth_acc or 'none'}")
        show = min(args.max_new, 16)
        for b in range(min(args.requests, 2)):
            row = np.asarray(toks[b, args.prompt_len:args.prompt_len + show])
            print(f"  row {b}: {row} ...")
        return

    if args.continuous:
        if args.no_draft:
            raise SystemExit("--continuous is speculative-only")
        # with a device trace too, the spans also land on its host plane
        tracer = (Tracer(annotate=bool(args.jax_profile))
                  if args.trace_out else None)
        registry = MetricsRegistry() if args.metrics_out else None
        engine, serve_reqs = build_continuous(
            args, served, rng, tracer=tracer, registry=registry)
        for r in serve_reqs:
            engine.submit(r)
        with jax_profile(args.jax_profile):
            results = engine.run()
        tel = engine.telemetry
        stats = [engine.stats[r.request_id] for r in results]
        total_new = sum(s.new_tokens for s in stats)
        span = max(s.finish_time_s for s in stats)
        tau = float(np.mean([s.sd.tau for s in stats]))
        print(f"continuous: {len(results)} requests, {total_new} tokens "
              f"in {span:.2f}s -> {total_new / span:.1f} tok/s")
        seq_draft_steps = tree.depth if tree is not None else args.gamma
        ttft = latency_percentiles([s.ttft_s for s in stats])
        tpot = latency_percentiles([s.tpot_s for s in stats])
        print(f"  tau={tau:.3f} MBSU={mbsu(tau, c, seq_draft_steps):.3f} "
              f"TTFT p50={ttft['p50_ms']:.0f}ms p99={ttft['p99_ms']:.0f}ms "
              f"TPOT p50={tpot['p50_ms']:.0f}ms p99={tpot['p99_ms']:.0f}ms")
        print(f"  steps={tel.steps} rounds={tel.decode_rounds} "
              f"prefill_chunks={tel.prefill_chunks} "
              f"max_queue={tel.max_queue_depth} "
              f"mean_active={tel.mean_active_rows:.2f}")
        print(f"  kv pages read/spanned={tel.kv_pages_read}"
              f"/{tel.kv_pages_spanned} ({tel.kv_read_share:.3f})")
        if engine.prefix is not None:
            print(f"  prefix cache: {engine.prefix.tel.summary()} "
                  f"shared_page_frac={tel.mean_shared_frac:.2f}")
        pooled = SDStats()
        for s in stats:
            pooled.merge(s.sd)
        depth_acc = ", ".join(f"d{d}={r:.2f}"
                              for d, r in pooled.depth_acceptance().items())
        print(f"  pooled tau={pooled.tau:.3f} "
              f"({pooled.tokens_per_s():.1f} tok/s-per-slot) "
              f"per-depth acceptance: {depth_acc or 'none'}")
        # tokens-committed-per-round distribution (accept_hist): the full
        # shape behind tau — h spans 1..span (accepted drafts + bonus)
        hist = " ".join(f"{h}:{n}"
                        for h, n in sorted(pooled.accept_hist.items()))
        print(f"  tokens-per-round histogram: {hist or 'none'}")
        if args.quality_telemetry:
            q = engine.quality_stats
            print("  " + q.summary().replace("\n", "\n  "))
            curve = " ".join(
                f"H<={hi:g}:{rate:.2f}(tvd {tv:.2f})" if np.isfinite(hi)
                else f"H>4:{rate:.2f}(tvd {tv:.2f})"
                for hi, _, rate, tv in q.acceptance_entropy_curve())
            print(f"  accept-vs-entropy: {curve or 'none'}")
            for tenant, ts in sorted(engine.tenant_quality.items()):
                if tenant:
                    print(f"  tenant {tenant}: accept={ts.accept_rate:.3f} "
                          f"mean_tvd={ts.mean_tvd:.3f} "
                          f"alarms={ts.drift_alarms}")
            rep = acceptance_report(q, seq_draft_steps)
            print("  " + format_acceptance_report(rep).replace("\n", "\n  "))
        if engine.slo_tracker is not None:
            print("  " + engine.slo_tracker.summary().replace("\n", "\n  "))
        if engine.recorder is not None:
            rc = engine.recorder
            print(f"  flight recorder: {rc.rounds_seen} rounds ringed "
                  f"(cap {rc.capacity}), {len(rc.triggers)} triggers, "
                  f"{len(rc.dumped_paths)} bundles in {rc.out_dir}/")
        if registry is not None:
            pooled.emit(registry, prefix="sd_pooled")
        if args.metrics_out:
            engine.finalize_metrics()
            prom = args.metrics_out + ".prom"
            with open(prom, "w") as f:
                f.write(registry.to_prometheus())
            print(f"  metrics: {args.metrics_out} (JSONL) + {prom} "
                  "(Prometheus exposition)")
        if tracer is not None:
            tracer.write(args.trace_out)
            print(f"  trace: {args.trace_out} ({len(tracer.events())} events"
                  " — load at https://ui.perfetto.dev)")
        return

    lens = prompt_lengths(args, rng)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size,
                                        lens[i]).astype(np.int32),
                    max_new_tokens=args.max_new, request_id=i)
            for i in range(args.requests)]
    engine = ServingEngine(
        target=target, target_params=t_params,
        draft=None if args.no_draft else draft,
        draft_params=None if args.no_draft else d_params, sd=sdc)
    results = engine.serve(reqs)
    tau = float(np.mean([r.tau for r in results]))
    print(f"served {len(results)} requests; tau={tau:.3f} "
          f"MBSU={mbsu(tau, c, args.gamma):.3f}")
    for r in results[:2]:
        print(f"  req {r.request_id}: {r.tokens[:16]} ... {r.wall_time_s:.2f}s")


if __name__ == "__main__":
    main()
