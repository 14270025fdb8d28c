"""The acceptance construction: served logits of target and drafter have
std about 0.99 (a tied head included, after its rescale), so speculative
sampling at temperature 1 accepts about half of the drafted tokens; and the
served weights are the values the reference regenerates."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import run  # noqa: E402
from lib import weights  # noqa: E402
from lib.draw import base_key, draw  # noqa: E402


def _config(tied):
    """A reduced width of the benchmark's layout, on the CPU."""
    return {"name": "acc", "hidden_size": 512, "intermediate_size": 1024,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "head_dim": 64, "num_hidden_layers": 2, "vocab_size": 4096,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": tied, "torch_dtype": "bfloat16",
            "drafter": {"num_hidden_layers": 1, "hidden_size": 256,
                        "num_attention_heads": 4, "num_key_value_heads": 2,
                        "head_dim": 64, "intermediate_size": 512,
                        "tie_word_embeddings": False}}


@pytest.mark.parametrize("tied", [True, False])
def test_served_logits_have_unit_scale(tied):
    models = run.program_models(_config(tied))
    params = weights.build(models, base_key(2 ** 35 + 11))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 4096, (2, 64)),
                       jnp.int32)
    for (model, _, _), p in zip(models, params):
        logits, _ = model.logits(p, toks)
        std = float(jnp.std(logits.astype(jnp.float32)))
        assert 0.93 < std < 1.05, std


def test_served_weights_are_the_reference_draws():
    cfg = _config(True)
    models = run.program_models(cfg)
    key = base_key(7)
    tp, dp = weights.build(models, key)
    dims = models[0][1]
    wq1 = draw(key, dims, "target", "attn.wq", 1).astype(jnp.bfloat16)
    assert jnp.array_equal(tp["groups"][0]["attn"]["wq"][1], wq1)
    emb = draw(key, dims, "target", "embed").astype(jnp.bfloat16)
    assert jnp.array_equal(tp["embed"], emb)
    assert "lm_head" not in tp and "lm_head" in dp
    # the tied table is drawn at 1/sqrt(d), an untied one at 1
    assert float(jnp.std(tp["embed"].astype(jnp.float32))) == \
        pytest.approx(0.987 / np.sqrt(512), rel=0.02)
    assert float(jnp.std(dp["embed"].astype(jnp.float32))) == \
        pytest.approx(0.987, rel=0.02)
