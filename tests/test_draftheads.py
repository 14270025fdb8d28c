"""Self-speculative draft heads (repro.draftheads): temp-0 equivalence of
both head families in chain and tree rounds, the continuous engine without a
drafter KV pool, head distillation, and checkpoint round-trips."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_draft_heads, save_draft_heads
from repro.configs.base import ModelConfig
from repro.core.speculative import (SDConfig, autoregressive_generate,
                                    speculative_generate)
from repro.draftheads import (HeadConfig, HeadDrafter, finetune_heads,
                              is_head_drafter, make_head_train_state)
from repro.draftheads.heads import medusa_logits
from repro.models import Model
from repro.models.model import capture_hidden
from repro.spectree import TreeSpec, tree_speculative_generate

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
            attn_chunk=16, remat=False)
TCFG = ModelConfig(name="t", arch_type="dense", num_layers=4, **BASE)


@pytest.fixture(scope="module")
def target():
    t = Model(TCFG)
    tp, _ = t.init(jax.random.PRNGKey(0))
    return t, tp


@pytest.fixture(scope="module")
def drafters():
    out = {}
    for i, kind in enumerate(("eagle", "medusa")):
        h = HeadDrafter(HeadConfig.for_target(kind, TCFG, num_medusa_heads=4))
        out[kind] = (h, h.init(jax.random.PRNGKey(2 + i)))
    return out


def _prompt(B=2, S=8):
    return jax.random.randint(jax.random.PRNGKey(5), (B, S), 3,
                              BASE["vocab_size"])


# ------------------------------------------------- temp-0 exactness (core)

@pytest.mark.parametrize("kind", ["eagle", "medusa"])
def test_chain_temp0_matches_ar(target, drafters, kind):
    """Greedy speculative decoding with a draft head is token-identical to
    target-only greedy AR — rejection sampling guarantees it for ANY head."""
    t, tp = target
    drafter, hp = drafters[kind]
    prompt = _prompt()
    max_new = 24
    ar, _ = autoregressive_generate(t, tp, prompt, max_new, temperature=0.0)
    sd, stats = speculative_generate(drafter, t, hp, tp, prompt, max_new,
                                     SDConfig(gamma=3, temperature=0.0))
    S = prompt.shape[1] + max_new
    assert jnp.array_equal(sd[:, :S], ar[:, :S])
    assert stats.tau >= 1.0


@pytest.mark.parametrize("kind", ["eagle", "medusa"])
def test_tree_temp0_matches_ar(target, drafters, kind):
    t, tp = target
    drafter, hp = drafters[kind]
    prompt = _prompt()
    max_new = 24
    ar, _ = autoregressive_generate(t, tp, prompt, max_new, temperature=0.0)
    sd, stats = tree_speculative_generate(
        drafter, t, hp, tp, prompt, max_new,
        SDConfig(gamma=2, temperature=0.0), TreeSpec((2, 2)))
    S = prompt.shape[1] + max_new
    assert jnp.array_equal(sd[:, :S], ar[:, :S])
    assert stats.tau >= 1.0


def test_medusa_untrained_warm_start(target, drafters):
    """Medusa's near-zero residual init makes every head ~= the target's own
    next-token distribution at the hidden state it reads.

    Head k drafts position +k from the same hidden state the target's LM
    head reads, so at warm start a draft is accepted exactly where the
    target's greedy continuation repeats its current token. Whether a random
    target does that depends on the draw, so acceptance is checked on a
    target that repeats by construction: tied embeddings and blocks zeroed
    to the identity make every greedy step return the current token."""
    t, tp = target
    drafter, hp = drafters["medusa"]
    with capture_hidden() as box:
        lg, _ = t.logits(tp, _prompt())
    heads = medusa_logits(hp, tp, TCFG, drafter.hc, box["hidden"])
    # residual weights ~1e-2/sqrt(D) move logits by ~5e-3; bf16 logits near
    # |3| are rounded to 1/64
    assert jnp.allclose(heads, lg[..., None, :], atol=0.05), \
        float(jnp.max(jnp.abs(heads - lg[..., None, :])))

    cfg = TCFG.replace(tie_embeddings=True)
    rep = Model(cfg)
    rp, _ = rep.init(jax.random.PRNGKey(0))
    g = rp["groups"][0]
    g = {**g, "attn": {**g["attn"], "wo": jnp.zeros_like(g["attn"]["wo"])},
         "mlp": {**g["mlp"], "w_down": jnp.zeros_like(g["mlp"]["w_down"])}}
    rp = {**rp, "groups": (g,)}
    rep_heads = HeadDrafter(HeadConfig.for_target("medusa", cfg,
                                                  num_medusa_heads=4))
    _, stats = speculative_generate(rep_heads, rep,
                                    rep_heads.init(jax.random.PRNGKey(3)),
                                    rp, _prompt(), 32,
                                    SDConfig(gamma=3, temperature=0.0))
    assert stats.tau > 1.05, stats.tau


# --------------------------------------------------------- continuous engine

def test_continuous_engine_with_heads(target, drafters):
    """Heads in the continuous engine: no drafter page pool, chunked prefill
    seeds h_feat, and greedy output matches target AR exactly."""
    from repro.serving import ContinuousEngine, ServeRequest
    t, tp = target
    drafter, hp = drafters["eagle"]
    engine = ContinuousEngine(
        target=t, target_params=tp, draft_heads=drafter, draft_head_params=hp,
        sd=SDConfig(gamma=2, temperature=0.0), max_batch=2, max_seq_len=28,
        page_size=8, prefill_chunk=8)
    assert "d_cache" not in engine._state and "h_feat" in engine._state
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, BASE["vocab_size"], 12).astype(np.int32)
               for _ in range(2)]
    for i, p in enumerate(prompts):
        engine.submit(ServeRequest(prompt=p, max_new_tokens=10, request_id=i))
    results = sorted(engine.run(), key=lambda r: r.request_id)
    assert len(results) == 2
    for i, r in enumerate(results):
        ar, _ = autoregressive_generate(
            t, tp, jnp.asarray(prompts[i])[None], 10, temperature=0.0)
        assert np.array_equal(np.asarray(r.tokens),
                              np.asarray(ar[0, 12:22])), i


def test_continuous_engine_rejects_both_drafters(target, drafters):
    from repro.serving import ContinuousEngine
    t, tp = target
    drafter, hp = drafters["eagle"]
    with pytest.raises(ValueError):
        ContinuousEngine(target=t, target_params=tp,
                         draft=Model(TCFG), draft_params=tp,
                         draft_heads=drafter, draft_head_params=hp,
                         sd=SDConfig(gamma=2), max_batch=2, max_seq_len=28)


# ------------------------------------------------------------- validation

def test_medusa_gamma_exceeds_heads_raises(drafters):
    drafter, _ = drafters["medusa"]
    drafter.validate_chain(4)                      # K == 4: fine
    with pytest.raises(ValueError):
        drafter.validate_chain(5)
    with pytest.raises(ValueError):
        drafter.validate_tree(5)
    eagle = HeadDrafter(HeadConfig.for_target("eagle", TCFG))
    eagle.validate_chain(16)                       # autoregressive: any gamma


def test_head_drafter_duck_typing(drafters):
    assert is_head_drafter(drafters["eagle"][0])
    assert is_head_drafter(drafters["medusa"][0])
    assert not is_head_drafter(Model(TCFG))


# -------------------------------------------------------- hidden-state tap

def test_capture_hidden_matches_backbone(target):
    t, tp = target
    toks = _prompt()
    with capture_hidden() as box:
        logits, _ = t.logits(tp, toks)
    h = box["hidden"]
    assert h.shape == (*toks.shape, TCFG.d_model)
    # the tap records the final-norm output the logits are projected from
    from repro.models import transformer as tfm
    ref = tfm.logits_from_hidden(tp, h, TCFG)
    assert jnp.allclose(logits, ref, atol=1e-5)


def test_prefill_return_hidden(target):
    t, tp = target
    toks = _prompt()
    logits, _, h = t.prefill(tp, toks, cache_len=32, return_hidden=True)
    assert h.shape == (*toks.shape, TCFG.d_model)
    # prefill's logits are the last position's, projected from h[:, -1]
    from repro.models import transformer as tfm
    assert jnp.allclose(tfm.logits_from_hidden(tp, h[:, -1:], TCFG), logits,
                        atol=1e-5)


# ------------------------------------------------------------ distillation

def test_finetune_heads_smoke(target):
    """A few TVD++ distillation steps run, produce finite losses, and move
    the head parameters."""
    t, tp = target
    from repro.configs.base import TrainConfig
    steps, B, S = 4, 4, 16
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=steps,
                     batch_size=B, seq_len=S)
    chunks = np.random.default_rng(0).integers(
        3, BASE["vocab_size"], (B * steps, S)).astype(np.int32)
    batches = (chunks[B * s:B * (s + 1)] for s in range(steps))
    for kind in ("eagle", "medusa"):
        drafter = HeadDrafter(HeadConfig.for_target(kind, TCFG,
                                                    num_medusa_heads=4))
        hstate = make_head_train_state(drafter, jax.random.PRNGKey(7))
        before = jax.tree.map(lambda x: x.copy(), hstate["params"])
        if kind == "medusa":
            batches = (chunks[B * s:B * (s + 1)] for s in range(steps))
        hstate, hist = finetune_heads(drafter, t, hstate, tp, batches, tc,
                                      steps, loss_kind="tvdpp", log_every=1)
        assert len(hist) == steps
        assert all(np.isfinite(m["loss"]) for m in hist)
        moved = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), before,
            hstate["params"]))
        assert max(moved) > 0.0, kind


# -------------------------------------------------------------- checkpoint

def test_save_load_roundtrip(tmp_path, drafters):
    drafter, hp = drafters["eagle"]
    path = str(tmp_path / "heads.npz")
    save_draft_heads(path, drafter, hp)
    restored = load_draft_heads(path, drafter)
    for a, b in zip(jax.tree.leaves(hp), jax.tree.leaves(restored)):
        assert jnp.array_equal(a, b)


def test_load_config_mismatch_raises(tmp_path, drafters):
    drafter, hp = drafters["eagle"]
    path = str(tmp_path / "heads.npz")
    save_draft_heads(path, drafter, hp)
    other = HeadDrafter(dataclasses.replace(drafter.hc, num_heads=2))
    with pytest.raises(ValueError, match="mismatch"):
        load_draft_heads(path, other)


def test_param_count_matches_init(drafters):
    for kind, (drafter, hp) in drafters.items():
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(hp))
        assert n == drafter.hc.param_count(), kind
