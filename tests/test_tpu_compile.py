"""The Pallas kernels compile for a TPU v5e at real widths.

Interpret mode cannot see the TPU's tiling rules (a block's last two dims
must tile (8, 128) or span the array), so every kernel is compiled here with
``interpret=False`` for one chip of a described v5e topology — the chip's
own compiler, no chip needed. The topology is described inside a fixture,
never at import: only the process that runs these tests loads the TPU
library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import distill_loss, flash_decode, paged_attention, \
    quant_matmul, tree_attention

B, S, HD = 8, 1024, 128
TREE_NODES = 7                     # a (2, 2) tree: root + 2 + 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_compile_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in hlo      # the kernel, not a fallback
    return run


@pytest.mark.parametrize("hkv,g", [(8, 3), (32, 1)])
def test_tree_attention_compiles(compile_tpu, hkv, g):
    compile_tpu(
        lambda q, k, v, m: tree_attention.tree_attention(q, k, v, m,
                                                         interpret=False),
        ((B, hkv, TREE_NODES, g, HD), jnp.bfloat16),
        ((B, S, hkv, HD), jnp.bfloat16), ((B, S, hkv, HD), jnp.bfloat16),
        ((B, TREE_NODES, S), jnp.bool_))


@pytest.mark.parametrize("hkv,g", [(8, 3), (32, 1)])
def test_flash_decode_compiles(compile_tpu, hkv, g):
    compile_tpu(
        lambda q, k, v, m: flash_decode.flash_decode(q, k, v, m,
                                                     interpret=False),
        ((B, hkv, g, HD), jnp.bfloat16),
        ((B, S, hkv, HD), jnp.bfloat16), ((B, S, hkv, HD), jnp.bfloat16),
        ((B, S), jnp.bool_))


@pytest.mark.parametrize("b,t,h,hkv,layers,P", [
    (32, 4, 24, 8, 32, 1200), (32, 1, 8, 4, 4, 1200),
    (1, 256, 24, 8, 32, 1200),
    (32, 4, 8, 1, 48, 4096), (32, 1, 2, 1, 4, 4096),
    (1, 256, 8, 1, 48, 4096)],
    ids=["target_verify", "drafter_feed", "target_prefill",
         "one_head_target_verify", "one_head_drafter_feed",
         "one_head_target_prefill"])
def test_paged_attention_compiles(compile_tpu, b, t, h, hkv, layers, P):
    """phi4-chat's widths: 1,200 pages of 16 slots, 257-page tables; and
    one chip's share of yi9b-chat's pools split by head over four chips:
    one bf16 KV head of 4,096 pages."""
    page, max_pages = 16, 257
    pool = ((layers, P, page, hkv, HD), jnp.bfloat16)
    compile_tpu(
        lambda q, k, v, pp, tab, pos: paged_attention.paged_attention(
            q, k, v, pp, tab, pos, 3, interpret=False),
        ((b, t, h, HD), jnp.bfloat16), pool, pool,
        ((layers, P, page), jnp.int32), ((b, max_pages), jnp.int32),
        ((b, t), jnp.int32))


def test_paged_kernel_under_shard_map_compiles(topo, no_compile_cache,
                                              monkeypatch):
    """Across the four chips of a v5e 2x2 the paged kernel runs per chip
    under ``shard_map`` (a Mosaic call cannot be partitioned
    automatically): Yi-9B's verify, q split on heads and the pools on KV
    heads, one bf16 head a chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import ops
    from repro.models import attention
    from repro.sharding.context import use_mesh
    monkeypatch.setattr(ops, "INTERPRET", False)   # Mosaic, as on the chip
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    heads, pools, rep = (NamedSharding(mesh, P(None, None, "model")),
                         NamedSharding(mesh, P(None, None, None, "model")),
                         NamedSharding(mesh, P()))
    pool = (48, 4096, 16, 4, HD)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in [
        ((32, 4, 32, HD), jnp.bfloat16, heads),
        (pool, jnp.bfloat16, pools), (pool, jnp.bfloat16, pools),
        ((48, 4096, 16), jnp.int32, rep), ((32, 257), jnp.int32, rep),
        ((32, 4), jnp.int32, rep)]]
    with use_mesh(mesh, (), "model"):
        hlo = jax.jit(lambda *a: attention._paged_kernel(
            *a, 3, None, None)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" not in hlo and "all-gather" not in hlo


@pytest.mark.parametrize("bits,group", [(8, 0), (4, 64)])
def test_quant_matmul_compiles(compile_tpu, bits, group):
    K, N = 3072, 8192
    if bits == 8:
        w, s = ((K, N), jnp.int8), ((1, N), jnp.float32)
    else:
        w, s = ((K // 2, N), jnp.uint8), ((K // group, N), jnp.float32)
    compile_tpu(
        lambda x, q, sc: quant_matmul.quant_matmul(
            x, q, sc, bits=bits, group=group, interpret=False),
        ((8, K), jnp.bfloat16), w, s)


def test_row_logsumexp_compiles(compile_tpu):
    compile_tpu(lambda x: distill_loss.row_logsumexp(x, interpret=False),
                ((512, 32000), jnp.float32))


def test_loss_terms_compiles(compile_tpu):
    f = jnp.float32
    compile_tpu(
        lambda s, t, ls, lt, mu, isg: distill_loss.loss_terms(
            s, t, ls, lt, mu, isg, interpret=False),
        ((512, 32000), f), ((512, 32000), f), ((512,), f), ((512,), f),
        ((), f), ((), f))
