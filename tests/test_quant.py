"""Quantized decode path (repro.quant): kernel-vs-oracle sweeps, checkpoint
round-trip, int8-KV dense/paged consistency, temp-0 speculative invariants,
and the tree-attention fast-path dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io
from repro.configs.base import ModelConfig, QuantConfig
from repro.core.speculative import (SDConfig, autoregressive_generate,
                                    speculative_generate)
from repro.kernels import ref
from repro.kernels.quant_matmul import quant_matmul as quant_matmul_kernel
from repro.models import attention as A
from repro.models.model import Model
from repro.quant import (QWeight, decode_step_bytes, dequantize,
                         quantize_kv_cache, quantize_params, quantize_weight)

KEY = jax.random.PRNGKey(0)

TCFG = ModelConfig(name="qt", arch_type="dense", num_layers=4, d_model=128,
                   num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=256,
                   attn_chunk=32, remat=False)
DCFG = TCFG.replace(name="qd", num_layers=2)


def models():
    target, draft = Model(TCFG), Model(DCFG)
    tp, _ = target.init(jax.random.PRNGKey(0))
    dp, _ = draft.init(jax.random.PRNGKey(1))
    return target, draft, tp, dp


# ------------------------------------------------------ kernel vs oracle

@pytest.mark.parametrize("m,k,n", [(4, 128, 256), (130, 64, 96), (8, 384, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_int8_sweep(m, k, n, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k))).astype(dtype)
    qw = quantize_weight(rng.normal(size=(k, n)).astype(np.float32), bits=8)
    got = quant_matmul_kernel(x, qw.q, qw.scale, bits=8, group=0)
    want = ref.ref_quant_matmul(x, qw.q, qw.scale, 8, 0)
    atol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(got, want, atol=atol * float(jnp.max(jnp.abs(want)) + 1))


@pytest.mark.parametrize("group", [32, 64])
@pytest.mark.parametrize("k,n", [(128, 256), (384, 128)])
def test_quant_matmul_int4_grouped_sweep(group, k, n):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, k)), jnp.float32)
    qw = quantize_weight(rng.normal(size=(k, n)).astype(np.float32),
                         bits=4, group=group)
    got = quant_matmul_kernel(x, qw.q, qw.scale, bits=4, group=group)
    want = ref.ref_quant_matmul(x, qw.q, qw.scale, 4, group)
    assert jnp.allclose(got, want, atol=1e-5 * float(jnp.max(jnp.abs(want)) + 1))


def test_quant_matmul_matches_fp_within_tolerance():
    """int8 per-channel quantization reconstructs the fp matmul to ~1%."""
    rng = np.random.default_rng(2)
    w = rng.normal(size=(256, 512)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
    qw = quantize_weight(w, bits=8)
    got = quant_matmul_kernel(x, qw.q, qw.scale, bits=8, group=0)
    want = x @ jnp.asarray(w)
    assert jnp.allclose(got, want, rtol=1e-2,
                        atol=1e-2 * float(jnp.max(jnp.abs(want))))


def test_awq_pre_scale_roundtrip():
    """AWQ pre-scale: x @ dequantize(qw) == ref oracle with pre applied."""
    rng = np.random.default_rng(3)
    w = rng.normal(size=(128, 64)).astype(np.float32)
    amax = np.abs(rng.normal(size=(128,))) + 0.1
    qw = quantize_weight(w, bits=8, act_amax=amax)
    assert qw.pre is not None
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    want = ref.ref_quant_matmul(x, qw.q, qw.scale, 8, 0, pre=qw.pre)
    assert jnp.allclose(x @ dequantize(qw), want, atol=1e-5)


# ------------------------------------------------------ model-level PTQ

def test_quantized_params_logit_error_small():
    _, draft, _, dp = models()
    calib = np.asarray(jax.random.randint(KEY, (8, 32), 3, 256))
    toks = jnp.asarray(calib[:4])
    lg_fp, _ = draft.logits(dp, toks)
    for qcfg, bound in [(QuantConfig(weights="int8"), 0.5),
                        (QuantConfig(weights="int4", group_size=32), 2.5)]:
        qdp = quantize_params(draft, dp, qcfg, calib_tokens=calib)
        lg_q, _ = draft.logits(qdp, toks)
        err = float(jnp.max(jnp.abs(lg_fp - lg_q)))
        spread = float(jnp.max(lg_fp) - jnp.min(lg_fp))
        assert err < bound * spread / 10 + 1.0, (qcfg.weights, err)


def test_quantize_save_load_roundtrip(tmp_path):
    _, draft, _, dp = models()
    qcfg = QuantConfig(weights="int4", group_size=32)
    qdp = quantize_params(draft, dp, qcfg)
    path = str(tmp_path / "q.npz")
    io.save_quantized(path, qdp)
    like = quantize_params(draft, dp, qcfg)
    loaded = io.load_quantized(path, like)
    for a, b in zip(jax.tree.leaves(qdp), jax.tree.leaves(loaded)):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b)
    # layout mismatch fails loudly
    like8 = quantize_params(draft, dp, QuantConfig(weights="int8"))
    with pytest.raises(ValueError, match="layout mismatch"):
        io.load_quantized(path, like8)


def test_save_load_restores_awq_pre_scale(tmp_path):
    """A calibrated (pre-bearing) checkpoint loaded into an UNCALIBRATED
    template must restore the AWQ pre-scale — pre=None is an empty pytree
    subtree, so without reconciliation the 1/s compensation silently
    vanishes and the loaded model computes x @ (s*W)."""
    _, draft, _, dp = models()
    calib = np.asarray(jax.random.randint(KEY, (4, 24), 3, 256))
    qdp = quantize_params(draft, dp, QuantConfig(weights="int8"),
                          calib_tokens=calib)
    path = str(tmp_path / "awq.npz")
    io.save_quantized(path, qdp)
    like = quantize_params(draft, dp, QuantConfig(weights="int8"))  # no calib
    loaded = io.load_quantized(path, like)
    toks = jnp.asarray(calib[:2])
    lg_saved, _ = draft.logits(qdp, toks)
    lg_loaded, _ = draft.logits(loaded, toks)
    assert jnp.allclose(lg_saved, lg_loaded, atol=1e-5)


def test_quantize_params_weights_none_is_noop():
    _, draft, _, dp = models()
    out = quantize_params(draft, dp, QuantConfig())
    for a, b in zip(jax.tree.leaves(dp), jax.tree.leaves(out)):
        assert jnp.array_equal(a, b)


def test_quantized_leaves_are_qweights():
    _, draft, _, dp = models()
    qdp = quantize_params(draft, dp, QuantConfig(weights="int8"))
    nodes = jax.tree_util.tree_flatten_with_path(
        qdp, is_leaf=lambda x: isinstance(x, QWeight))[0]
    names = {str(p[-1]) for p, n in nodes if isinstance(n, QWeight)}
    assert any("wq" in n for n in names) and any("lm_head" in n for n in names)
    # int8 leaves actually store int8
    qws = [n for _, n in nodes if isinstance(n, QWeight)]
    assert qws and all(w.q.dtype == jnp.int8 for w in qws)


def test_quantize_shared_attn_sets():
    """zamba2-style shared-attention sets (stacked (nsets, K, N) leaves) are
    quantized per set into stacked QWeights that _select_shared can index."""
    from repro.configs.base import ATTN, SHARED_ATTN
    cfg = TCFG.replace(name="qs", arch_type="hybrid",
                       layer_pattern=(ATTN, SHARED_ATTN),
                       num_shared_attn_sets=2)
    m = Model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    qp = quantize_params(m, p, QuantConfig(weights="int8"))
    nodes = jax.tree_util.tree_flatten_with_path(
        qp["shared_attn"], is_leaf=lambda x: isinstance(x, QWeight))[0]
    qws = [n for _, n in nodes if isinstance(n, QWeight)]
    assert len(qws) == 7 and all(w.q.shape[0] == 2 for w in qws)  # qkv/o+swiglu
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 3, 256)
    lg_fp, _ = m.logits(p, toks)
    lg_q, _ = m.logits(qp, toks)
    spread = float(jnp.max(lg_fp) - jnp.min(lg_fp))
    assert float(jnp.max(jnp.abs(lg_fp - lg_q))) < 0.05 * spread


# ------------------------------------------------------ int8 KV cache

def test_kv_quant_dense_decode_close_to_fp():
    target, _, tp, _ = models()
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 3, 256)
    lg, cache = target.prefill(tp, prompt, cache_len=64)
    qcache = quantize_kv_cache(cache)
    tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
    pos = jnp.full((2, 1), 16, jnp.int32)
    lg_fp, _ = target.decode_step(tp, tok, pos, cache)
    lg_q, _ = target.decode_step(tp, tok, pos, qcache)
    spread = float(jnp.max(lg_fp) - jnp.min(lg_fp))
    assert float(jnp.max(jnp.abs(lg_fp - lg_q))) < 0.05 * spread + 0.1


def test_kv_quant_paged_matches_dense():
    """int8-KV paged decode == int8-KV dense decode (same tokens/positions).

    Per-slot scales depend only on the entry itself, so physical placement
    (ring slot vs page slot) cannot change the dequantized view."""
    target, _, tp, _ = models()
    B, P, page, max_pages = 2, 9, 8, 4
    dense = target.init_cache(B, max_pages * page, kv_quant=True)
    pool = target.init_paged_cache(P, page, kv_quant=True)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, 20), 3, 256)
    lg_d = lg_p = None
    for t in range(20):
        pos = jnp.full((B, 1), t, jnp.int32)
        lg_d, dense = target.decode_step(tp, toks[:, t:t + 1], pos, dense)
        lg_p, pool = target.decode_step(tp, toks[:, t:t + 1], pos, pool,
                                        page_table=table)
    assert jnp.allclose(lg_d, lg_p, atol=1e-4)


def test_temp0_token_match_quantized_drafter():
    """SD correctness invariant: with a quantized DRAFTER (fp target), temp-0
    speculative output is token-identical to the target's greedy AR output —
    drafter quantization may only change tau, never the tokens."""
    target, draft, tp, dp = models()
    calib = np.asarray(jax.random.randint(KEY, (4, 24), 3, 256))
    qdp = quantize_params(draft, dp, QuantConfig(weights="int8"),
                          calib_tokens=calib)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 3, 256)
    sdc = SDConfig(gamma=3, temperature=0.0)
    out, stats = speculative_generate(draft, target, qdp, tp, prompt, 24, sdc)
    ar, _ = autoregressive_generate(target, tp, prompt, 24, temperature=0.0)
    assert bool(jnp.all(out[:, :36] == ar[:, :36]))
    assert stats.tau >= 1.0            # bonus token always commits


def test_temp0_match_rate_with_kv_quant():
    """int8 KV on BOTH models perturbs the verifier itself, so exactness is
    no longer guaranteed — but the match rate must stay near 1.

    The models compute in float32: the bf16 logits of these random models
    tie exactly (top-2 margin 0) on a greedy step, where any perturbation
    flips the argmax and the free-running sequences part; a tie says nothing
    about the int8 KV error this test bounds."""
    target = Model(TCFG.replace(dtype="float32"))
    draft = Model(DCFG.replace(dtype="float32"))
    tp, _ = target.init(jax.random.PRNGKey(0))
    dp, _ = draft.init(jax.random.PRNGKey(1))
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 3, 256)
    sdc = SDConfig(gamma=3, temperature=0.0, kv_quant=True)
    out, _ = speculative_generate(draft, target, dp, tp, prompt, 24, sdc)
    ar, _ = autoregressive_generate(target, tp, prompt, 24, temperature=0.0)
    match = float(jnp.mean((out[:, :36] == ar[:, :36]).astype(jnp.float32)))
    assert match > 0.9, match


def test_continuous_engine_kv_quant_with_quantized_drafter():
    """ContinuousEngine(kv_quant=True) + int8 drafter: serves every request
    to completion through the int8 paged pool, and the first generated token
    (sampled straight off the chunked prefill) matches target greedy AR.
    Exact full-sequence match is NOT guaranteed here — int8 KV perturbs the
    target verifier itself and a single flipped argmax compounds; the
    numerical guarantee lives in test_kv_quant_paged_matches_dense."""
    from repro.serving import ContinuousEngine, ServeRequest
    target, draft, tp, dp = models()
    qdp = quantize_params(draft, dp, QuantConfig(weights="int8"))
    engine = ContinuousEngine(
        target=target, target_params=tp, draft=draft, draft_params=qdp,
        sd=SDConfig(gamma=2, temperature=0.0), max_batch=2, max_seq_len=28,
        page_size=8, prefill_chunk=8, kv_quant=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, 256, 12).astype(np.int32) for _ in range(2)]
    for i, p in enumerate(prompts):
        engine.submit(ServeRequest(prompt=p, max_new_tokens=10, request_id=i))
    results = sorted(engine.run(), key=lambda r: r.request_id)
    assert len(results) == 2
    for i, r in enumerate(results):
        assert len(r.tokens) == 10
        ar, _ = autoregressive_generate(
            target, tp, jnp.asarray(prompts[i])[None], 10, temperature=0.0)
        assert int(r.tokens[0]) == int(ar[0, 12])


# ------------------------------------------------------ tree fast path

def test_tree_fastpath_matches_sdpa(monkeypatch):
    """decode_attention with the Pallas tree kernel forced on == the pure
    JAX masked-_sdpa path (fp32 model for tight tolerance)."""
    cfg = TCFG.replace(dtype="float32")
    target = Model(cfg)
    tp, _ = target.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 3, 256)
    _, cache0 = target.prefill(tp, prompt, cache_len=64)
    N = 5
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, N), 3, 256)
    pos = 16 + jnp.asarray([[0, 1, 1, 2, 2]], jnp.int32).repeat(2, 0)
    slots = 16 + jnp.broadcast_to(jnp.arange(N)[None], (2, N))
    anc = jnp.asarray(np.array([[1, 0, 0, 0, 0], [1, 1, 0, 0, 0],
                                [1, 0, 1, 0, 0], [1, 1, 0, 1, 0],
                                [1, 0, 1, 0, 1]], bool))
    amask = jnp.ones((2, N, 64), bool)
    amask = amask.at[:, :, 16:16 + N].set(jnp.broadcast_to(anc[None], (2, N, N)))
    monkeypatch.setattr(A, "TREE_FASTPATH", False)
    lg_ref, _ = target.decode_step(tp, toks, pos, cache0, slots=slots,
                                   attn_mask=amask)
    monkeypatch.setattr(A, "TREE_FASTPATH", True)
    lg_k, _ = target.decode_step(tp, toks, pos, cache0, slots=slots,
                                 attn_mask=amask)
    assert jnp.allclose(lg_ref, lg_k, atol=2e-3), \
        float(jnp.max(jnp.abs(lg_ref - lg_k)))


def test_tree_fastpath_auto_respects_interpret():
    from repro.kernels import ops
    assert A.TREE_FASTPATH is None
    # interpret mode (CPU container): auto must pick the pure-JAX path
    assert A._use_tree_kernel(128) == (not ops.INTERPRET)


def test_tree_fastpath_untileable_window_raises(monkeypatch):
    """With compiled kernels, a KV width the tree kernel cannot tile raises
    instead of quietly running the _sdpa path; tileable widths dispatch."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "INTERPRET", False, raising=False)
    assert A._use_tree_kernel(96) and A._use_tree_kernel(256)
    with pytest.raises(ValueError, match="kv_window"):
        A._use_tree_kernel(200)


@pytest.mark.parametrize("page", [16, 24])
def test_tree_engine_window_tiles(page):
    """A tree engine sizes its gathered paged window so the tree kernel
    tiles it (a multiple of KV_TILE past one tile)."""
    from repro.kernels.tree_attention import KV_TILE
    from repro.serving import ContinuousEngine
    from repro.spectree import TreeSpec
    target, draft, tp, dp = models()
    engine = ContinuousEngine(
        target=target, target_params=tp, draft=draft, draft_params=dp,
        sd=SDConfig(gamma=2, temperature=0.0), tree=TreeSpec((2, 2)),
        max_batch=2, max_seq_len=144, page_size=page, prefill_chunk=16)
    width = engine.pool.max_pages_per_seq * page
    assert width > KV_TILE and width % KV_TILE == 0
    # the smallest such width: less than one lcm(page, tile) step of slack
    assert width - (144 + 8 + 16) < np.lcm(page, KV_TILE)


# ------------------------------------------------------ bytes model

def test_modeled_bytes_int8_at_least_2x():
    """Acceptance: >= 2x modeled weight+KV byte reduction for the paper's
    int8 drafter config (scale-vector overheads included)."""
    from repro.configs import get_config
    cfg = get_config("llama2-chat-drafter-115m")
    fp = decode_step_bytes(cfg, batch=8, ctx=2048,
                           weights=cfg.param_dtype, kv="bfloat16")
    q8 = decode_step_bytes(cfg, batch=8, ctx=2048, weights="int8", kv="int8")
    q4 = decode_step_bytes(cfg, batch=8, ctx=2048, weights="int4", kv="int8")
    assert fp.total / q8.total >= 2.0
    assert q4.total < q8.total
