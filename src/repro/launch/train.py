"""Training launcher.

Drives the pretrain or distill loop on the default device; it builds no
mesh (the sharded full-size step is compiled by launch/dryrun.py on virtual
CPU devices). ``--reduced`` selects the reduced same-family config; the
corpus is synthetic.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 50 --phase pretrain
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 50 --phase distill --loss tvdpp

Self-speculative draft heads (repro.draftheads) instead of a separate
drafter: ``--draft-head {eagle,medusa}`` distills the heads against the
target's live hidden states on target-generated responses (core.datagen)
mixed 9:1 with the pretraining stream; ``--save`` then writes a head
checkpoint (checkpoint.save_draft_heads) loadable by ``launch.serve
--draft-head ... --head-ckpt``:

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 50 --phase distill --loss tvdpp --draft-head eagle
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCHS, get_config, reduced
from ..configs.base import TrainConfig
from ..data import SyntheticCorpus, pack_documents, simple_batches, mixed_batches
from ..models.model import Model
from ..training import make_train_state, train, finetune
from ..checkpoint import save
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--phase", choices=("pretrain", "distill"), default="pretrain")
    ap.add_argument("--loss", default="tvdpp",
                    choices=("kld", "kld_bwd", "jsd", "tvd", "tvdpp"))
    ap.add_argument("--draft-head", choices=("eagle", "medusa"), default=None,
                    help="distill self-speculative draft heads instead of a "
                         "separate drafter (implies --phase distill)")
    ap.add_argument("--medusa-heads", type=int, default=4,
                    help="number of parallel Medusa heads (offsets +1..+K)")
    ap.add_argument("--datagen-seqs", type=int, default=8,
                    help="seed sequences for the datagen distillation set "
                         "(--draft-head only)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg)
    tc = TrainConfig(learning_rate=args.lr, warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps, batch_size=args.batch,
                     seq_len=args.seq)

    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=args.seed)
    chunks = pack_documents(corpus.pretrain_docs(600, args.seq * 2), args.seq)
    if cfg.num_codebooks > 1:   # audio: replicate stream per codebook
        chunks = np.repeat(chunks[:, None, :], cfg.num_codebooks, axis=1)

    state, _ = make_train_state(model, jax.random.PRNGKey(args.seed), tc)
    t0 = time.time()
    if args.draft_head is not None:
        if cfg.num_codebooks > 1:
            raise SystemExit("--draft-head supports single-codebook targets")
        from ..checkpoint import save_draft_heads
        from ..core.datagen import DatagenConfig, generate_distillation_dataset
        from ..draftheads import (HeadConfig, HeadDrafter, finetune_heads,
                                  make_head_train_state)
        drafter = HeadDrafter(HeadConfig.for_target(
            args.draft_head, cfg, num_medusa_heads=args.medusa_heads))
        t_params = state["params"]
        # distillation stream: target-generated responses on corpus seeds,
        # mixed 9:1 with the pretraining chunks (same recipe as --phase
        # distill for a separate drafter)
        seed_len = max(args.seq // 2, 1)
        seeds = np.asarray(chunks[:args.datagen_seqs, :seed_len], np.int32)
        data = generate_distillation_dataset(
            model, t_params, seeds,
            DatagenConfig(temperatures=(0.0, 0.7),
                          max_response_tokens=args.seq - seed_len,
                          batch_size=args.datagen_seqs))
        hstate = make_head_train_state(drafter,
                                       jax.random.PRNGKey(args.seed + 1))
        hstate, hist = finetune_heads(
            drafter, model, hstate, t_params,
            mixed_batches(data, chunks, args.batch, mix=tc.distill_mix),
            tc, args.steps, loss_kind=args.loss,
            log_every=max(args.steps // 5, 1),
            callback=lambda s, m: print(f"step {s}: {m}"))
        print(f"done in {time.time()-t0:.1f}s "
              f"({args.draft_head} heads, {drafter.hc.param_count()} params)")
        if args.save:
            save_draft_heads(args.save, drafter, hstate["params"])
            print(f"saved {args.draft_head} head params -> {args.save}")
        return
    if args.phase == "pretrain":
        state, hist = train(model, state, simple_batches(chunks, args.batch),
                            tc, args.steps, log_every=max(args.steps // 5, 1),
                            callback=lambda s, m: print(f"step {s}: {m}"))
    else:
        tgt_cfg = cfg
        d_cfg = cfg.drafter() if not args.reduced else cfg.replace(
            name=cfg.name + "-draft", num_layers=max(cfg.num_layers // 2, 1))
        draft = Model(d_cfg)
        dstate, _ = make_train_state(draft, jax.random.PRNGKey(args.seed + 1), tc)
        t_params = state["params"]
        dstate, hist = finetune(
            draft, model, dstate, t_params,
            mixed_batches(chunks, chunks, args.batch, mix=tc.distill_mix),
            tc, args.steps, loss_kind=args.loss,
            log_every=max(args.steps // 5, 1),
            callback=lambda s, m: print(f"step {s}: {m}"))
        state = dstate
    print(f"done in {time.time()-t0:.1f}s")
    if args.save:
        save(args.save, state["params"])
        print(f"saved params -> {args.save}")


if __name__ == "__main__":
    main()
