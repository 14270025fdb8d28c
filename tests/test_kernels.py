"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels import distill_loss as dk
from repro.kernels.ops import fused_distill_loss, flash_decode_attention

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("n,v", [(8, 512), (16, 1024), (32, 2048), (8, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_logsumexp_sweep(n, v, dtype):
    x = (jax.random.normal(KEY, (n, v)) * 3).astype(dtype)
    got = dk.row_logsumexp(x)
    want = ref.ref_logsumexp(x)
    assert jnp.allclose(got, want, atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("mode", ["kld", "tvd", "tvdpp"])
@pytest.mark.parametrize("n,v", [(8, 512), (24, 1024)])
def test_loss_terms_sweep(mode, n, v):
    s = jax.random.normal(KEY, (n, v))
    t = jax.random.normal(jax.random.PRNGKey(1), (n, v)) * 1.5
    lse_s, lse_t = ref.ref_logsumexp(s), ref.ref_logsumexp(t)
    mu, isg = jnp.asarray(0.3), jnp.asarray(2.0)
    got = dk.loss_terms(s, t, lse_s, lse_t, mu, isg, mode=mode)
    want = ref.ref_loss_terms(s, t, mu, isg, mode=mode)
    for g, w in zip(got, want):
        assert jnp.allclose(g, w, atol=1e-4), mode


@pytest.mark.parametrize("mode", ["kld", "tvd", "tvdpp"])
def test_loss_grad_kernel(mode):
    n, v = 16, 512
    s = jax.random.normal(KEY, (n, v))
    t = jax.random.normal(jax.random.PRNGKey(1), (n, v)) * 1.5
    lse_s, lse_t = ref.ref_logsumexp(s), ref.ref_logsumexp(t)
    mu, isg = jnp.asarray(0.2), jnp.asarray(1.5)
    _, c, _, _ = ref.ref_loss_terms(s, t, mu, isg, mode=mode)
    g_rows = jax.random.uniform(jax.random.PRNGKey(2), (n,))
    got = dk.loss_grad(s, t, lse_s, lse_t, c, g_rows, mu, isg, mode=mode)
    want = ref.ref_loss_grad(s, t, c, g_rows, mu, isg, mode=mode)
    assert jnp.allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("mode", ["kld", "tvd", "tvdpp"])
@pytest.mark.parametrize("n,v", [(16, 512), (8, 1536)])
def test_fused_loss_value_and_grad_vs_reference(mode, n, v):
    s = jax.random.normal(KEY, (n, v))
    t = jax.random.normal(jax.random.PRNGKey(1), (n, v)) * 2.0
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (n,)) > 0.25).astype(jnp.float32)
    vk, gk = jax.value_and_grad(lambda x: fused_distill_loss(mode, x, t, mask))(s)
    vr, gr = jax.value_and_grad(lambda x: ref.ref_distill_loss(mode, x, t, mask))(s)
    assert abs(float(vk - vr)) < 1e-5
    assert float(jnp.max(jnp.abs(gk - gr))) < 1e-6


def test_fused_loss_jits():
    s = jax.random.normal(KEY, (8, 512))
    t = jax.random.normal(jax.random.PRNGKey(1), (8, 512))
    mask = jnp.ones((8,))
    f = jax.jit(lambda a, b, m: fused_distill_loss("tvdpp", a, b, m))
    assert jnp.isfinite(f(s, t, mask))


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("g", [1, 3, 4])
@pytest.mark.parametrize("s_len", [128, 384])
def test_flash_decode_sweep(hd, g, s_len):
    B, Hkv = 2, 2
    q = jax.random.normal(KEY, (B, Hkv, g, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, s_len, Hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, s_len, Hkv, hd))
    lens = jnp.array([s_len // 2, s_len])[:, None]
    mask = jnp.arange(s_len)[None] < lens
    got = flash_decode_attention(q, k, v, mask)
    want = ref.ref_flash_decode(q, k, v, mask)
    assert jnp.allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtype_and_softcap(dtype):
    B, Hkv, g, hd, s_len = 1, 2, 2, 64, 256
    q = jax.random.normal(KEY, (B, Hkv, g, hd)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, s_len, Hkv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, s_len, Hkv, hd)).astype(dtype)
    mask = jnp.ones((B, s_len), bool)
    got = flash_decode_attention(q, k, v, mask, softcap=20.0)
    want = ref.ref_flash_decode(q, k, v, mask, softcap=20.0)
    assert jnp.allclose(got, want, atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_flash_decode_matches_model_decode_attention():
    """Kernel path == the jnp decode attention used by the serving engine."""
    import math
    from repro.configs.base import ModelConfig
    from repro.models import attention as A

    cfg = ModelConfig(name="x", arch_type="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=32,
                      head_dim=64)
    params, _ = A.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 2, 128
    kc = jax.random.normal(KEY, (B, S, 2, 64))
    vc = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, 64))
    cpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    cpos = jnp.where(cpos < 100, cpos, -1)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, 64))
    pos = jnp.full((B, 1), 100, jnp.int32)
    out_ref, _ = A.decode_attention(params, x, {"k": kc, "v": vc, "pos": cpos},
                                    pos, cfg)
    # kernel path on the same q/k/v (post insertion)
    q, k, v = A._project_qkv(params, x, cfg, pos)
    kc2 = kc.at[jnp.arange(B)[:, None], pos % S].set(k)
    vc2 = vc.at[jnp.arange(B)[:, None], pos % S].set(v)
    cpos2 = cpos.at[jnp.arange(B)[:, None], pos % S].set(pos)
    mask = (cpos2 >= 0) & (cpos2 <= 100)
    qg = q.reshape(B, 1, 2, 2, 64)[:, 0]      # (B, Hkv, g, hd), kv-major
    out_k = flash_decode_attention(qg, kc2, vc2, mask)
    out_k = out_k.reshape(B, 4, 64).reshape(B, 1, 256)
    out_k = jnp.einsum("bsh,hd->bsd", out_k.astype(x.dtype), params["wo"])
    assert jnp.allclose(out_ref, out_k, atol=1e-4)


def test_interpret_mode_resolution(monkeypatch):
    """Interpret mode only on the CPU backend or when asked for; nothing
    else falls back to the interpreter."""
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops._env_interpret() == (jax.default_backend() == "cpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops._env_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops._env_interpret() is True


# ------------------------------------------------------ paged attention

def _paged_rows(T, page=8, max_pages=6, P=20, L=2):
    """A pool and table in the engine's conventions: page 0 is null, each
    row's slots up to its last query position are written, rows differ in
    length. Rows: last query at 7 (a page's last slot), at 8 (one slot into
    the next page), sharing row 0's first page then running to 20, inactive
    (all-null table), and long (37) with stale writes past its queries.
    A chunk of more than 8 queries (prefill) starts rows 0 and 1 at
    position 0 and 1, and the table and pool grow to hold it."""
    import numpy as np
    rng = np.random.default_rng(0)
    lasts = [max(7, T - 1), max(8, T), 20 + T - 1, 5, 37 + T - 1]
    max_pages = max(max_pages, (lasts[-1] + 4) // page + 1)
    P = max(P, 3 * max_pages)
    pp = np.full((L, P, page), -1, np.int32)
    tab = np.zeros((len(lasts), max_pages), np.int32)
    nxt = 1
    for b, last in enumerate(lasts):
        if b == 3:
            continue
        written = last + (4 if b == 4 else 0)
        for j in range(written // page + 1):
            if b == 2 and j == 0:
                tab[b, 0] = tab[0, 0]       # shared, read-only prefix page
                continue
            tab[b, j], nxt = nxt, nxt + 1
            for s in range(page):
                if j * page + s <= written:
                    pp[:, tab[b, j], s] = j * page + s
    pos = np.array(lasts)[:, None] - T + 1 + np.arange(T)[None]
    perm = rng.permutation(np.arange(1, P))      # physical ids out of order
    tab = np.where(tab > 0, perm[tab - 1], 0)
    pp_perm = np.full_like(pp, -1)
    pp_perm[:, perm] = pp[:, 1:]
    return jnp.asarray(pp_perm), jnp.asarray(tab), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("T,G,window,cap,dtype,npb,Hkv", [
    (1, 2, None, None, jnp.float32, 4, 2),
    (4, 3, None, None, jnp.float32, 4, 2),
    (4, 2, None, None, jnp.bfloat16, 4, 2),
    (1, 3, 11, None, jnp.float32, 2, 2),
    (4, 2, None, 5.0, jnp.float32, 4, 2),
    (4, 8, None, None, jnp.bfloat16, 4, 1),
    (4, 8, 11, None, jnp.bfloat16, 2, 1),
    (4, 8, None, None, jnp.bfloat16, None, 1),
    (4, 8, 11, None, jnp.bfloat16, None, 1),
    (64, 8, None, None, jnp.bfloat16, None, 1),
], ids=["t1_g2", "t4_g3", "t4_g2_bf16", "window", "softcap",
        "t4_g8_bf16_one_head", "window_bf16_one_head",
        "one_head_rule_tiling", "window_one_head_rule_tiling",
        "prefill_one_head_rule_tiling"])
def test_paged_attention_matches_gather(T, G, window, cap, dtype, npb, Hkv,
                                        monkeypatch):
    """The in-place paged kernel equals the gather + masked-softmax oracle
    (``ref.ref_paged_attention``); an all-null row reads zeros, not NaN.
    Four pages a block leave the 6-page table's last block half past its
    end; two a block let the window skip the long row's first block. A
    single 16-bit KV head (a chip's share of a pool split by head) is read
    without its head axis. Without ``npb`` the rule tiles the call, its
    byte target cut to 4 of these 512-byte pages (as 512 KB is 64 of
    yi9b-chat's 8-KB ones), so the long row spans two blocks and the
    others one; a prefill chunk of 512 query rows, two grid steps before
    the rule, then goes in one."""
    from repro.kernels import paged_attention as pk
    hd, L = 32, 2
    assert pk.supports(Hkv, dtype)
    page_pos, table, pos = _paged_rows(T)
    P, page = page_pos.shape[1:]
    if npb is None:
        size = jnp.dtype(dtype).itemsize
        monkeypatch.setattr(pk, "BLOCK_BYTES", 4 * 2 * page * Hkv * hd * size)
        rule = pk.tiling(page, Hkv, hd, size, T * G, table.shape[1])
        assert rule == (4, T * G)
    kk = jax.random.split(KEY, 3)
    k = jax.random.normal(kk[0], (L, P, page, Hkv, hd)).astype(dtype)
    v = jax.random.normal(kk[1], (L, P, page, Hkv, hd)).astype(dtype)
    q = jax.random.normal(kk[2], (table.shape[0], T, Hkv * G, hd)).astype(dtype)
    args = (q, k, v, page_pos, table, pos)
    got = pk.paged_attention(*args, layer=1, softcap=cap, window=window,
                             interpret=True, pages_per_block=npb)
    want = ref.ref_paged_attention(*args, layer=1, softcap=cap, window=window)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got[3]))) == 0.0          # inactive row
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert jnp.allclose(got, want, atol=tol), float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("t,h,hkv,want", [
    (4, 24, 8, (8, 12)), (1, 8, 4, (16, 2)), (256, 24, 8, None),
    (4, 8, 1, (64, 32)), (1, 2, 1, (64, 2)), (256, 8, 1, None)],
    ids=["phi4_verify", "phi4_drafter_feed", "phi4_prefill",
         "one_head_target_verify", "one_head_drafter_feed",
         "one_head_target_prefill"])
def test_paged_tiling_rule(t, h, hkv, want):
    """The tiling rule at the cells' shapes (page 16, hd 128, bf16, 257-page
    tables): phi4-chat's verify keeps the 8 pages and 12 rows it was tuned
    at; a block moves ``BLOCK_BYTES`` of K and V; rows a step divide the
    rows evenly; and a step's tiles, landing buffers and scores stay under
    the VMEM budget kernel_lint holds every kernel to."""
    from repro.analysis.kernel_lint import VMEM_BUDGET_BYTES
    from repro.kernels import paged_attention as pk
    page, hd, size, max_pages = 16, 128, 2, 257
    rows = t * (h // hkv)
    npb, rb = pk.tiling(page, hkv, hd, size, rows, max_pages)
    if want is not None:
        assert (npb, rb) == want
    assert npb * 2 * page * hkv * hd * size == pk.BLOCK_BYTES
    assert rows % rb == 0 and (rb == rows or rb % 16 == 0)
    assert pk.vmem_bytes(rb, npb, page, hkv, hd, size, max_pages) \
        <= pk.VMEM_BYTES < VMEM_BUDGET_BYTES
    assert pk.tiling(page, hkv, hd, size, rows, max_pages, 8)[0] == 8
