"""Sharding rules + roofline cost-model validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, reduced
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.roofline import (flops_model, model_flops, active_params,
                                   bytes_model)
from repro.sharding.rules import logical_to_pspec, make_param_shardings


class FakeMesh:
    shape = {"data": 4, "model": 2}


def test_logical_to_pspec_basic():
    assert logical_to_pspec(("fsdp", "tp"), FakeMesh, (8, 8)) == P("data", "model")
    assert logical_to_pspec((None, "tp"), FakeMesh, (8, 8)) == P(None, "model")


def test_logical_to_pspec_divisibility_fallback():
    # 6 % 4 != 0 -> data dropped; 8 % 2 == 0 -> model kept
    assert logical_to_pspec(("fsdp", "tp"), FakeMesh, (6, 8)) == P(None, "model")
    assert logical_to_pspec(("fsdp", "tp"), FakeMesh, (6, 7)) == P()


def test_make_param_shardings_structure(monkeypatch):
    """Sharding tree mirrors the params tree exactly (incl. tuples/dicts)."""
    devs = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devs).reshape(1, 1), ("data", "model"))
    from repro.models import Model
    cfg = reduced(get_config("yi-9b"))
    m = Model(cfg)
    params, specs = m.init(jax.random.PRNGKey(0))
    sh = make_param_shardings(specs, params, mesh)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda x: 0, sh))


def test_flops_model_scales_with_tokens():
    cfg = get_config("yi-9b")
    s1 = ShapeConfig("a", 1024, 8, "train")
    s2 = ShapeConfig("b", 1024, 16, "train")
    assert flops_model(cfg, s2) == pytest.approx(2 * flops_model(cfg, s1), rel=1e-6)


def test_model_flops_moe_counts_active_only():
    moe = get_config("grok-1-314b")
    dense_equiv = moe.replace(num_experts=0, num_experts_per_tok=0)
    s = INPUT_SHAPES["train_4k"]
    assert active_params(moe) < 0.5 * moe.param_count()
    assert model_flops(moe, s) < model_flops(dense_equiv, s) * 3


def test_flops_model_vs_cost_analysis_unrolled():
    """Validate the analytic flop model against XLA cost analysis on a tiny
    UNROLLED dense model (no scan => cost_analysis counts everything)."""
    from repro.models import transformer as tfm
    from repro.models.model import Model

    cfg = ModelConfig(name="v", arch_type="dense", num_layers=1, d_model=128,
                      num_heads=4, num_kv_heads=4, head_dim=32, d_ff=256,
                      vocab_size=256, attn_chunk=64, remat=False)
    m = Model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    B, S = 4, 64
    toks = jnp.zeros((B, S), jnp.int32)

    def fwd(p, t):
        lg, _ = m.logits(p, t)
        return lg.sum()

    compiled = jax.jit(fwd).lower(params, toks).compile()
    ca = compiled.cost_analysis()
    xla_flops = ca["flops"]
    shape = ShapeConfig("x", S, B, "prefill")
    ours = flops_model(cfg, shape)
    # prefill model counts head once per sequence; this fwd computes the head
    # for every position — adjust for comparison
    ours_full_head = ours + 2 * cfg.d_model * cfg.vocab_size * B * (S - 1)
    assert 0.5 < ours_full_head / xla_flops < 2.0, (ours_full_head, xla_flops)


def test_bytes_model_decode_dominated_by_cache_at_long_context():
    cfg = get_config("yi-9b")
    s = INPUT_SHAPES["decode_32k"]
    total = bytes_model(cfg, s, 256)
    p_local = cfg.param_count() * 4 / 256
    assert total > p_local   # cache adds real traffic
