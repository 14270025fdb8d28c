"""Parameter, K/V and round counts against hand counts, read from the
served layouts (shapes only, nothing allocated) of the benchmark's
configuration and of yi-9b, whole and at 24 layers, whose files wait in the
test data for the cell that will run it."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import run  # noqa: E402
from lib import counts, spec  # noqa: E402


DIRS = (Path(__file__).resolve().parents[1],
        Path(__file__).resolve().parent / "data")


def _served(name, pages=8, page=16):
    d = next(d for d in DIRS if (d / "configs" / f"{name}.json").exists())
    out = []
    for model, dims, _ in run.program_models(spec.load_config(name, d)):
        params = jax.eval_shape(lambda k, m=model: m.init(k)[0],
                                jax.random.PRNGKey(0))
        pool = jax.eval_shape(lambda m=model: m.init_paged_cache(pages, page))
        out.append((params, pool))
    return out


def _dense(layers, d, heads, kv_heads, hd, ff, vocab, tied):
    layer = (d * (heads + 2 * kv_heads) * hd + heads * hd * d + 3 * d * ff
             + 2 * d)
    return vocab * d * (1 if tied else 2) + layers * layer + d


@pytest.mark.parametrize("name,target,drafter,billions,kv", [
    ("phi4-mini-3.8b", (32, 3072, 24, 8, 128, 8192, 200064, True),
     (4, 1024, 8, 4, 128, 2816, 200064, False), (3.836, 0.457), 139_264),
    ("yi-9b-24l", (24, 4096, 32, 4, 128, 11008, 64000, False),
     (4, 1024, 8, 4, 128, 2816, 64000, False), (4.677, 0.178), 57_344),
    ("yi-9b", (48, 4096, 32, 4, 128, 11008, 64000, False),
     (4, 1024, 8, 4, 128, 2816, 64000, False), (8.829, 0.178), 106_496),
])
def test_params_and_kv_bytes_per_token(name, target, drafter, billions, kv):
    (tp, tpool), (dp, dpool) = _served(name)
    assert counts.param_count(tp) == _dense(*target)
    assert counts.param_count(dp) == _dense(*drafter)
    assert round(counts.param_count(tp) / 1e9, 3) == billions[0]
    assert round(counts.param_count(dp) / 1e9, 3) == billions[1]
    t, d = counts.model_counts(tp, tpool), counts.model_counts(dp, dpool)
    assert t.matmul_bytes == 2 * t.matmul_params      # served in bf16
    assert t.kv_bytes_per_token + d.kv_bytes_per_token == kv


def test_phi4_round_counts_by_hand():
    (tp, tpool), (dp, dpool) = _served("phi4-mini-3.8b")
    t, d = counts.model_counts(tp, tpool), counts.model_counts(dp, dpool)
    # tied head: the 200064 x 3072 table is read once as the head
    emb = 200064 * 3072
    layer = 3072 * (24 + 2 * 8) * 128 + 24 * 128 * 3072 + 3 * 3072 * 8192
    assert t.matmul_params == emb + 32 * layer
    assert t.matmul_bytes == 2 * t.matmul_params
    assert (t.layers, t.heads, t.head_dim) == (32, 24, 128)
    d_layer = 1024 * (8 + 2 * 4) * 128 + 8 * 128 * 1024 + 3 * 1024 * 2816
    assert d.matmul_params == 200064 * 1024 + 4 * d_layer
    g, ctxs = 3, [100, 1000]
    flops = sum(4 * (2 * t.matmul_params + 4 * 32 * 24 * 128 * c)
                + 4 * (2 * d.matmul_params + 4 * 4 * 8 * 128 * c)
                for c in ctxs)
    assert counts.round_flops(t, d, ctxs, g) == flops
    nbytes = (t.matmul_bytes + 4 * d.matmul_bytes
              + 1100 * (131_072 + 4 * 8_192))
    assert counts.round_bytes(t, d, ctxs, g) == nbytes
