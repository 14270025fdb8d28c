"""Decide ``correct``: replay the served sampling decisions against the
reference.

The server samples at temperature T > 0, so a served token is not the
reference's argmax. But every draw it makes is a Gumbel-max draw
(``jax.random.categorical``) with a key the harness can reconstruct, so each
decision can be replayed on the reference's logits with the same noise:

  prefill      first token = argmax(log q + G), q the target's distribution
               after the prompt;
  draft j      accepted draft x_j = argmax(log p_j + G_j), p_j the
               drafter's distribution (round key split j);
  accept j     x_j kept iff u_j < q_j(x_j) / p_j(x_j) (split gamma);
  residual     after a rejection the new token comes from
               norm(max(q - p, 0)) (split gamma + 1);
  bonus        after gamma acceptances it comes from q_{gamma+1}.

Each decision gives a gap in nats, 0 where the reference makes the served
choice:
  Gumbel choices  max_v(l(v) + G(v)) - (l(x) + G(x)), l the reference's
                  log-probabilities: the widest gap by which the served
                  token lies below the reference's best;
  accept/reject   |log u - log r| where the reference's ratio r decides
                  the other way;
  residual        the Gumbel choice over log(q - p), with each token given
                  the benefit of a rounding band: the served x scored at
                  log(q e^b - p e^-b), every other token at
                  log(max(q e^-b - p e^b, 0)). log(q - p) is ill-conditioned
                  near q = p, where the server's bfloat16 rounding alone
                  moves it; the band ``RESIDUAL_BAND`` (b) absorbs errors of
                  the server's log-probabilities up to b, and a residual
                  drawn from the wrong distribution still reads nats off.

Teacher-forced on the served tokens, so one early difference does not
cascade. The control puts its own choices (from lower-precision logits) at
the same decision points and is measured by the same gaps.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

#: nats of error in the server's log-probabilities that the residual's
#: comparison absorbs: bfloat16 logits of magnitude 4 to 8 are spaced 2^-5
#: apart, and the largest gap of a sound run between two tokens' errors
#: was 0.055 (PERF.md), so 0.06 covers either token's error twice over
RESIDUAL_BAND = 0.06


@dataclass
class Production:
    """Tokens one call of the server produced for one request."""
    kind: str            # "prefill" | "round"
    key: object          # the key its draws used (prefill: the split key;
                         # round: the round key, split gamma + 2 ways)
    tokens: List[int]
    complete: bool = True  # False where the stream may have been cut


@dataclass
class Sample:
    """One finished request, as served."""
    rid: int
    slot: int
    prompt: np.ndarray
    productions: List[Production] = field(default_factory=list)

    @property
    def served(self) -> np.ndarray:
        return np.asarray([t for p in self.productions for t in p.tokens],
                          np.int64)


def wanted_positions(s: Sample) -> np.ndarray:
    """Positions whose next-token distributions the replay reads: the last
    prompt position and every served position but the last."""
    n_p, n_s = len(s.prompt), len(s.served)
    return np.arange(n_p - 1, n_p + n_s - 1)


@jax.jit
def log_probs(logits, inv_t):
    """The sampler's log-probabilities: log(max(softmax(l / T), 1e-30))."""
    return jnp.log(jnp.maximum(jax.nn.softmax(logits * inv_t, -1), 1e-30))


@jax.jit
def _prefill(key, lt, lt_low, x):
    """First token, drawn from the target after the prompt (row 0) -> (gap
    of the served token x, gap of the control's choice)."""
    lt, lt_low = lt[0], lt_low[0]
    g = jax.random.gumbel(key, lt.shape, jnp.float32)
    s = lt + g
    return (jnp.max(s) - s[x],
            jnp.max(s) - s[jnp.argmax(lt_low + g)])


@partial(jax.jit, static_argnums=(2, 3, 4))
def round_noise(kr, slot, batch, gamma, vocab):
    """The round's noise for row ``slot``, drawn as the round draws it over
    all ``batch`` rows: Gumbel rows of the gamma draft draws and of the
    final draw (gamma + 1, V), and the gamma acceptance uniforms."""
    keys = jax.random.split(kr, gamma + 2)
    rows = [jax.random.gumbel(keys[j], (batch, vocab), jnp.float32)[slot]
            for j in list(range(gamma)) + [gamma + 1]]
    u = jax.random.uniform(keys[gamma], (gamma, batch))[:, slot]
    return jnp.stack(rows), u


def _take(lp, x):
    return jnp.take_along_axis(lp, x[:, None], -1)[:, 0]


def _residual_gap(lq, lp, g, x):
    """Gap of residual token x drawn with Gumbel row g from
    norm(max(q - p, 0)), q = exp(lq), p = exp(lp), within the band."""
    e = np.exp(RESIDUAL_BAND)
    q, p = jnp.exp(lq), jnp.exp(lp)
    lo = jnp.log(jnp.maximum(q / e - p * e, 1e-30)) + g
    hi = jnp.log(jnp.maximum(q[x] * e - p[x] / e, 1e-30)) + g[x]
    return jnp.maximum(0.0, jnp.max(lo) - hi)


@jax.jit
def _round_gaps(lt, ld, g, u, n_acc, xd, dec, xf):
    """Widest gap over one round's decision points. lt, ld: (gamma+1, V)
    reference log-probs of target and drafter at the round's positions; g
    the round's Gumbel rows; u its uniforms; xd (gamma,) the draft token
    per position where it was streamed, dec (gamma,) the accept decisions,
    xf the final token (residual or bonus).

    A kept draft x is judged by the larger of its draw's gap and, where the
    reference would reject it, |log u - log r(x)|. A rejected draft is
    never streamed, so a rejection is judged by the least total over all
    tokens x of draw gap plus |log u - log r(x)| where the reference would
    accept x: the smallest error of the reference that explains it."""
    gamma = u.shape[0]
    j = jnp.arange(gamma)
    s = ld[:gamma] + g[:gamma]                              # (gamma, V)
    dgap_all = jnp.max(s, -1, keepdims=True) - s            # (gamma, V)
    logr_all = lt[:gamma] - ld[:gamma]
    logu = jnp.log(u)[:, None]
    kept = jnp.maximum(_take(dgap_all, xd),
                       jnp.maximum(0.0, logu[:, 0] - _take(logr_all, xd)))
    rejected = jnp.min(dgap_all + jnp.maximum(0.0, logr_all - logu), -1)
    agap = jnp.where(dec, kept, rejected)
    agap = jnp.where(j <= n_acc, agap, 0.0)
    res_gap = _residual_gap(lt[n_acc], ld[n_acc], g[gamma], xf)
    sb = lt[gamma] + g[gamma]
    bonus_gap = jnp.max(sb) - sb[xf]
    fgap = jnp.where(n_acc < gamma, res_gap, bonus_gap)
    return jnp.maximum(jnp.max(agap), fgap)


@jax.jit
def _served_choices(toks, n_acc):
    """The served round's choices: n_acc kept drafts, then a rejection
    (whose draft is not streamed) unless all gamma were kept."""
    gamma = toks.shape[0] - 1
    return toks[:gamma], jnp.arange(gamma) < n_acc, toks[n_acc]


@jax.jit
def _control_choices(lt_low, ld_low, g, u, n_acc):
    """The control's choices at the same decision points, drawn with the
    same noise from its lower-precision log-probs."""
    gamma = u.shape[0]
    xd = jnp.argmax(ld_low[:gamma] + g[:gamma], -1)
    dec = jnp.log(u) < _take(lt_low[:gamma], xd) - _take(ld_low[:gamma], xd)
    q = jnp.exp(lt_low[n_acc])
    p = jnp.where(n_acc < gamma, jnp.exp(ld_low[n_acc]), 0.0)
    res = jnp.maximum(q - p, 0.0)
    mass = res.sum()
    dist = jnp.where(mass > 1e-9, res / jnp.maximum(mass, 1e-30), q)
    xf = jnp.argmax(jnp.log(jnp.maximum(dist, 1e-30)) + g[gamma])
    return xd, dec, xf


def _rows(x, a, n):
    return jax.lax.dynamic_slice_in_dim(x, a, n, 0)


@jax.jit
def _round_served(lt, ld, a, g, u, n_acc, toks):
    n = g.shape[0]
    rt, rd = _rows(lt, a, n), _rows(ld, a, n)
    return _round_gaps(rt, rd, g, u, n_acc, *_served_choices(toks, n_acc))


@jax.jit
def _round_control(lt, ld, lt_low, ld_low, a, g, u, n_acc):
    n = g.shape[0]
    rt, rd = _rows(lt, a, n), _rows(ld, a, n)
    choice = _control_choices(_rows(lt_low, a, n), _rows(ld_low, a, n), g, u,
                              n_acc)
    return _round_gaps(rt, rd, g, u, n_acc, *choice)


def sample_gaps(s: Sample, lt, ld, batch: int, gamma: int,
                lt_low=None, ld_low=None):
    """Widest gap of one served request's decisions (and of the control's,
    where its log-probs are given). lt, ld, lt_low, ld_low: log-probs at
    ``wanted_positions(s)``, row k predicting served token k, with at least
    gamma + 1 rows past the last served token. -> (gap, control gap or
    None, number of decision points)."""
    control = lt_low is not None
    gaps, cgaps, n_dec = [], [], 0
    a = 0                                  # served index of the production
    for prod in s.productions:
        c = len(prod.tokens)
        if prod.complete and prod.kind == "prefill":
            g, cg = _prefill(prod.key, lt, lt_low if control else lt,
                             prod.tokens[0])
            gaps.append(g)
            cgaps.append(cg)
            n_dec += 1
        elif prod.complete:
            n_acc = c - 1
            g, u = round_noise(prod.key, s.slot, batch, gamma, lt.shape[1])
            toks = np.zeros((gamma + 1,), np.int32)
            toks[:c] = prod.tokens
            gaps.append(_round_served(lt, ld, a, g, u, n_acc, toks))
            if control:
                cgaps.append(_round_control(lt, ld, lt_low, ld_low, a, g, u,
                                            n_acc))
            n_dec += 2 * n_acc + (1 if n_acc < gamma else 0) + 1
        a += c
    gap = float(jnp.max(jnp.stack(gaps))) if gaps else float("nan")
    cgap = float(jnp.max(jnp.stack(cgaps))) if control and cgaps else None
    return gap, cgap, n_dec
