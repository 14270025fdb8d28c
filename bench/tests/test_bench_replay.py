"""The replay of one round's decisions (``lib/replay.py``) on synthetic
log-probabilities: a round drawn as the server draws it reads 0, and a
residual token drawn from q instead of norm(max(q - p, 0)) reads nats off."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import replay  # noqa: E402

GAMMA, BATCH, SLOT, VOCAB = 3, 4, 1, 64


def _round(seed, residual_from_q=False):
    """One served round over reference log-probs lt, ld, drawn as the
    chain round draws it. -> (lt, ld, key, tokens, n_acc)."""
    k_l, k_r = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k_l, (2, GAMMA + 1, VOCAB))
    lt, ld = (replay.log_probs(x, 1.0) for x in logits)
    g, u = replay.round_noise(k_r, SLOT, BATCH, GAMMA, VOCAB)
    xd = jnp.argmax(ld[:GAMMA] + g[:GAMMA], -1)
    ratio = jnp.exp(lt[jnp.arange(GAMMA), xd] - ld[jnp.arange(GAMMA), xd])
    n_acc = int(jnp.cumprod(u < ratio).sum())
    q = jnp.exp(lt[n_acc])
    p = jnp.exp(ld[n_acc]) if n_acc < GAMMA else jnp.zeros_like(q)
    dist = q if residual_from_q else jnp.maximum(q - p, 0.0)
    xf = int(jnp.argmax(jnp.log(jnp.maximum(dist, 1e-30)) + g[GAMMA]))
    toks = [int(x) for x in xd[:n_acc]] + [xf]
    return lt, ld, k_r, toks, n_acc


def _gap(lt, ld, key, toks):
    s = replay.Sample(0, SLOT, np.zeros((1,), np.int32),
                      [replay.Production("round", key, toks)])
    pad = jnp.zeros((GAMMA + 1, VOCAB))
    return replay.sample_gaps(s, jnp.concatenate([lt, pad]),
                              jnp.concatenate([ld, pad]), BATCH, GAMMA)[0]


@pytest.mark.parametrize("seed", range(6))
def test_a_round_drawn_as_served_reads_zero(seed):
    lt, ld, key, toks, _ = _round(seed)
    assert _gap(lt, ld, key, toks) == 0.0


def test_a_residual_drawn_from_q_reads_nats_off():
    wrong = []
    for seed in range(40):
        lt, ld, key, toks, n_acc = _round(seed, residual_from_q=True)
        right = _round(seed)[3]
        if n_acc < GAMMA and toks != right:
            wrong.append(_gap(lt, ld, key, toks))
    # a draw near a tie of the two distributions can fall inside the band;
    # most land outside the residual's support altogether
    assert len(wrong) >= 8
    assert np.mean(np.asarray(wrong) > 0.3) >= 0.75
    assert max(wrong) > 10.0
