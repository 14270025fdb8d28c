"""Serving engine + end-to-end system behaviour (replaces the scaffold
placeholder in test_system.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.speculative import SDConfig
from repro.models import Model
from repro.serving import Request, ServingEngine

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
            attn_chunk=16, remat=False)


@pytest.fixture(scope="module")
def models():
    tcfg = ModelConfig(name="t", arch_type="dense", num_layers=4, **BASE)
    dcfg = ModelConfig(name="d", arch_type="dense", num_layers=2, **BASE)
    t, d = Model(tcfg), Model(dcfg)
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    return t, d, tp, dp


def test_engine_serves_all_requests(models):
    t, d, tp, dp = models
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 64, 8).astype(np.int32),
                    max_new_tokens=12, request_id=i) for i in range(5)]
    eng = ServingEngine(target=t, target_params=tp, draft=d, draft_params=dp,
                        sd=SDConfig(gamma=3, temperature=0.0), batch_size=2)
    results = eng.serve(reqs)
    assert sorted(r.request_id for r in results) == list(range(5))
    for r in results:
        assert r.tokens.shape == (12,)
        assert r.tau >= 1.0


def test_engine_sd_equals_ar_mode_greedy(models):
    t, d, tp, dp = models
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, 8).astype(np.int32) for _ in range(2)]
    reqs = [Request(prompt=p, max_new_tokens=10, request_id=i)
            for i, p in enumerate(prompts)]
    sd = ServingEngine(target=t, target_params=tp, draft=d, draft_params=dp,
                       sd=SDConfig(gamma=3, temperature=0.0)).serve(reqs)
    ar = ServingEngine(target=t, target_params=tp,
                       sd=SDConfig(temperature=0.0)).serve(reqs)
    for a, b in zip(sorted(sd, key=lambda r: r.request_id),
                    sorted(ar, key=lambda r: r.request_id)):
        assert np.array_equal(a.tokens, b.tokens)


def test_multicodebook_decode_consistency():
    """musicgen-family: prefill+decode equals full forward (all codebooks)."""
    cfg = ModelConfig(name="mg", arch_type="audio", num_layers=2,
                      num_codebooks=4, **BASE)
    m = Model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, 64)
    _, cache = m.prefill(params, toks, cache_len=20)
    nxt = toks[:, :, :1]
    pos = jnp.full((2, 1), 16, jnp.int32)
    lg, _ = m.decode_step(params, nxt, pos, cache)
    full = jnp.concatenate([toks, nxt], axis=-1)
    lg_full, _ = m.logits(params, full)
    assert lg.shape == (2, 1, 4, 64)
    assert jnp.allclose(lg[:, 0], lg_full[:, 16], atol=1e-4)


def test_end_to_end_micro_pipeline():
    """Tiny run of the paper pipeline: must complete and improve draft CE."""
    from repro.experiments import run_pipeline
    res = run_pipeline(pretrain_steps=20, draft_pretrain_steps=14,
                       finetune_steps=8, ckpt_every=4, n_seeds_per_task=2,
                       eval_prompts=2, eval_new_tokens=10, sft_steps=6,
                       losses=("tvdpp",), gammas=(3,), batch=8, verbose=False)
    assert res.c_ratio < 0.2
    assert "tvdpp" in res.tau
    for task in ("dolly", "cnndm", "xsum"):
        assert 1.0 <= res.tau["tvdpp"][task]["3"] <= 4.0
    assert res.ood["base"] >= 1.0


def test_serve_builds_bf16_weights_under_jit():
    """launch.serve holds served weights in the compute dtype (bf16), drawn
    under one jit with the same values as the eager float32 init cast."""
    from repro.launch import serve
    args = serve.parse_args(["--arch", "phi4-mini-3.8b", "--reduced"])
    served = serve.build_models(args, np.random.default_rng(0))
    for params in (served.t_params, served.d_params):
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            want = jnp.float32 if "norm" in str(path) else jnp.bfloat16
            assert leaf.dtype == want, path
    eager, _ = Model(served.cfg.replace(param_dtype="float32")).init(
        jax.random.PRNGKey(0))
    for got, ref in zip(jax.tree.leaves(served.t_params),
                        jax.tree.leaves(eager)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref.astype(got.dtype),
                                                 np.float32))


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise one fixed
    directory at the root of the checkout."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        root = Path(__file__).resolve().parents[1]
        assert CACHE_DIR == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
