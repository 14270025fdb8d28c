"""Token sampling: temperature / top-p / greedy, plus SD residual sampling.

Matches the paper's decoding configs: distillation datagen samples at
temperatures {0, 0.3, 0.7, 1.0} with top-p 0.95; Dolly-style eval uses
temperature 0.6 / top-p 0.9; summarization eval is greedy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("sampling")
def probs_from_logits(logits, temperature: float = 1.0, top_p: float = 1.0):
    """logits (..., V) -> sampling distribution (..., V), fp32.

    temperature == 0 -> one-hot argmax (greedy).
    """
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        idx = jnp.argmax(logits, -1)
        return jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32)
    p = jax.nn.softmax(logits / temperature, -1)
    if top_p < 1.0:
        sorted_p = jnp.sort(p, -1)[..., ::-1]
        csum = jnp.cumsum(sorted_p, -1)
        # smallest set with cumulative mass >= top_p; a float32 cumsum can
        # stop short of a top_p just below 1, which would index past V
        cutoff_idx = jnp.minimum(jnp.sum(csum < top_p, -1, keepdims=True),
                                 p.shape[-1] - 1)
        cutoff = jnp.take_along_axis(sorted_p, cutoff_idx, -1)
        p = jnp.where(p >= cutoff, p, 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    return p


@jax.named_scope("sampling")
def sample_from_probs(key, probs):
    """Categorical sample; probs (..., V) -> ids (...)."""
    return jax.random.categorical(key, jnp.log(jnp.maximum(probs, 1e-30)), -1)


def sample(key, logits, temperature: float = 1.0, top_p: float = 1.0):
    p = probs_from_logits(logits, temperature, top_p)
    return sample_from_probs(key, p), p


@jax.named_scope("sampling")
def residual_sample(key, q, p):
    """Leviathan rejection-sampling residual: sample from norm(max(q - p, 0)).

    Falls back to q when the residual has no mass (p == q).
    """
    res = jnp.maximum(q - p, 0.0)
    mass = res.sum(-1, keepdims=True)
    dist = jnp.where(mass > 1e-9, res / jnp.maximum(mass, 1e-30), q)
    return sample_from_probs(key, dist)
