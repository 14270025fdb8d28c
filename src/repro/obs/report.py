"""Acceptance attribution: measured per-depth acceptance and tau against
the paper's i.i.d.-acceptance model."""
from __future__ import annotations


def acceptance_report(quality, gamma: int) -> dict:
    """Measured acceptance structure vs the paper's i.i.d.-acceptance model.

    The paper (and most speculative-decoding analysis) models block
    efficiency assuming a single per-position acceptance rate alpha applied
    i.i.d. along the chain: tau_iid = (1 - alpha^(gamma+1)) / (1 - alpha).
    Real acceptance is *depth-dependent* (drafts compound their own errors,
    so conditional acceptance decays with depth) — this report puts the
    measured per-depth conditional acceptance next to the flat alpha, and
    the measured tau next to the model's prediction, quantifying how much
    the i.i.d. assumption over- or under-states the drafter.

    ``quality`` is a ``repro.obs.quality.QualityStats``; returns per-depth
    rows plus (tau_measured, tau_iid, alpha).
    """
    att = quality.attempted.astype(float)
    acc = quality.accepted.astype(float)
    tot_att, tot_acc = att.sum(), acc.sum()
    alpha = float(tot_acc / tot_att) if tot_att else float("nan")
    rounds = max(quality.rounds, 1)
    # measured tau: 1 (pending/bonus always commits) + mean accepted/round;
    # survival S(d) = accepted[d-1] / rounds reconstructs it exactly
    tau_meas = 1.0 + float(tot_acc) / rounds
    if alpha == alpha and abs(1.0 - alpha) > 1e-9:
        tau_iid = (1.0 - alpha ** (gamma + 1)) / (1.0 - alpha)
    else:
        tau_iid = float(gamma + 1) if alpha == alpha else float("nan")
    depths = []
    for d in range(quality.depth):
        if att[d] == 0:
            continue
        cond = float(acc[d] / att[d])
        depths.append({"depth": d + 1,
                       "attempted": int(att[d]),
                       "conditional_acceptance": cond,
                       "iid_alpha": alpha,
                       "survival": float(acc[d] / rounds)})
    return {"alpha": alpha, "tau_measured": tau_meas, "tau_iid": tau_iid,
            "gamma": gamma, "rounds": quality.rounds, "depths": depths}


def format_acceptance_report(rep: dict) -> str:
    if not rep["depths"]:
        return "acceptance attribution: no attempted draft positions"
    lines = [(f"acceptance attribution over {rep['rounds']} rounds: "
              f"tau={rep['tau_measured']:.3f} vs i.i.d. model "
              f"{rep['tau_iid']:.3f} (alpha={rep['alpha']:.3f}, "
              f"gamma={rep['gamma']})")]
    for row in rep["depths"]:
        delta = row["conditional_acceptance"] - row["iid_alpha"]
        lines.append(
            f"  depth {row['depth']}: accept|reached="
            f"{row['conditional_acceptance']:.3f} ({delta:+.3f} vs alpha) "
            f"survival={row['survival']:.3f} n={row['attempted']}")
    return "\n".join(lines)
