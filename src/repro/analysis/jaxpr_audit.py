"""Jaxpr invariant auditor: trace the speculative rounds, assert invariants.

The repo's speed-up claims rest on properties of the *compiled* round that
unit tests only witness dynamically: the fused round must be one device
program with no host callbacks hiding inside, the state it returns must have
exactly the avals it consumed (or feeding state back each round forks the
jit cache), declared donation must actually alias every state buffer, and
the chain/tree/quant variants must agree on the dtypes of the leaves they
share (or a config flip forks the cache again). All of these are visible at
trace time on CPU: this module traces each round variant to a jaxpr / lowers
it to StableHLO and checks the invariants statically — no accelerator, no
execution of the round itself.

Rules
  JX001  forbidden primitive inside a round (callback / debug print /
         infeed-outfeed — anything that re-enters the host mid-round)
  JX002  round output state aval differs from its input state aval
         (shape / dtype / weak_type drift -> jit cache fork per round)
  JX003  declared donation not applied: fewer input->output buffer aliases
         in the lowering than state leaves
  JX004  dtype / weak_type drift between round variants for a same-named
         state leaf (chain vs tree vs quant would not share cache entries
         they should, and host code reading the leaves sees dtype flips)

Entry points: ``build_audit_subjects()`` constructs tiny-model round
subjects (chain, tree, quant-KV, head-drafter, and an engine-shaped paged
state); ``run_jaxpr_audit()`` applies every rule and returns a
``FindingSet``. Seeded-violation fixtures in tests build synthetic
``AuditSubject``s to prove each rule fires.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .findings import Finding, FindingSet

# Primitives that re-enter the host from inside a traced round. Any of
# these inside sd_round/tree_round breaks the one-fused-program contract:
# the device pipeline stalls on the host round-trip every round.
FORBIDDEN_PRIMITIVES: Dict[str, str] = {
    "pure_callback": "host callback (jax.pure_callback)",
    "io_callback": "host callback (jax.experimental.io_callback)",
    "debug_callback": "host callback (jax.debug.callback)",
    "debug_print": "host callback (jax.debug.print)",
    "custom_transpose_call": "host re-entry via custom_transpose",
    "infeed": "device infeed (host dependency mid-program)",
    "outfeed": "device outfeed (host dependency mid-program)",
}


@dataclass
class AuditSubject:
    """One round variant to audit.

    ``fn`` is the *un-jitted* round callable (model/config already closed
    over), ``args`` its example arguments — concrete arrays or
    ``ShapeDtypeStruct``s; tracing never executes the round either way.
    ``state_argnum`` locates the recurrent state pytree within ``args``.
    """

    name: str
    fn: Callable
    args: Tuple
    state_argnum: int = 2
    # JX004 compares dtypes only within a group: the int8-KV variant is
    # *meant* to store different cache dtypes than the fp variants
    dtype_group: str = "fp"


def _leaf_key(path) -> str:
    return jax.tree_util.keystr(path)


def _aval_map(tree) -> Dict[str, Tuple]:
    """Leaf path -> (shape, dtype, weak_type) for a pytree of array avals."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[_leaf_key(path)] = (tuple(leaf.shape), jnp.dtype(leaf.dtype),
                                bool(getattr(leaf, "weak_type", False)))
    return out


def iter_primitives(jaxpr):
    """Yield (primitive_name, eqn) over a jaxpr and all nested sub-jaxprs
    (pjit bodies, scan/while carries, cond branches, custom_* calls)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_primitives(sub)


def _sub_jaxprs(param):
    """Extract jaxprs nested inside an eqn param (covers ClosedJaxpr,
    bare Jaxpr, and lists/tuples of either — cond branches)."""
    import jax.extend.core as jcore
    vals = param if isinstance(param, (list, tuple)) else [param]
    out = []
    for v in vals:
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
    return out


# ------------------------------------------------------------------ rules

def audit_forbidden_primitives(subj: AuditSubject) -> List[Finding]:
    """JX001: no host-callback / infeed primitives anywhere in the round."""
    jaxpr = jax.make_jaxpr(subj.fn)(*subj.args)
    found: Dict[str, int] = {}
    for name, _ in iter_primitives(jaxpr.jaxpr):
        if name in FORBIDDEN_PRIMITIVES:
            found[name] = found.get(name, 0) + 1
    return [
        Finding(checker="jaxpr", rule="JX001", location=subj.name,
                message=f"{FORBIDDEN_PRIMITIVES[name]} primitive "
                        f"'{name}' x{n} inside the round — the fused device "
                        f"program would stall on the host every round",
                data={"subject": subj.name, "primitive": name, "count": n})
        for name, n in sorted(found.items())
    ]


def audit_state_aval_stability(subj: AuditSubject) -> List[Finding]:
    """JX002: output state avals == input state avals, leaf for leaf.

    The drivers feed each round's output state into the next round; any
    shape/dtype/weak_type drift means round 2 traces a *different* signature
    than round 1 — a per-round recompile the benchmarks would only see as
    mysteriously slow steady state.
    """
    in_state = subj.args[subj.state_argnum]
    out = jax.eval_shape(subj.fn, *subj.args)
    out_state = out[0] if isinstance(out, tuple) else out
    want, got = _aval_map(in_state), _aval_map(out_state)
    findings = []
    for key in sorted(set(want) | set(got)):
        if key not in got:
            findings.append(Finding(
                checker="jaxpr", rule="JX002", location=f"{subj.name}{key}",
                message=f"state leaf {key} consumed but not returned — "
                        f"output pytree structure differs from input",
                data={"subject": subj.name, "leaf": key, "kind": "missing"}))
        elif key not in want:
            findings.append(Finding(
                checker="jaxpr", rule="JX002", location=f"{subj.name}{key}",
                message=f"state leaf {key} returned but never consumed — "
                        f"output pytree structure differs from input",
                data={"subject": subj.name, "leaf": key, "kind": "extra"}))
        elif want[key] != got[key]:
            w, g = want[key], got[key]
            findings.append(Finding(
                checker="jaxpr", rule="JX002", location=f"{subj.name}{key}",
                message=f"state leaf {key} drifts across the round: "
                        f"in shape={w[0]} dtype={w[1]} weak_type={w[2]} vs "
                        f"out shape={g[0]} dtype={g[1]} weak_type={g[2]} — "
                        f"feeding state back forks the jit cache every round",
                data={"subject": subj.name, "leaf": key,
                      "in": {"shape": list(w[0]), "dtype": str(w[1]),
                             "weak_type": w[2]},
                      "out": {"shape": list(g[0]), "dtype": str(g[1]),
                              "weak_type": g[2]}}))
    return findings


def audit_donation(subj: AuditSubject) -> List[Finding]:
    """JX003: donating the state must alias EVERY state buffer in->out.

    The engine and both generate drivers run the round with
    ``donate_argnums=(state,)``; the lowering records each applied alias as
    a ``tf.aliasing_output`` parameter attribute. Fewer aliases than state
    leaves means some buffer is silently double-allocated — the KV pool
    (the big one) would exist twice.
    """
    lowered = jax.jit(subj.fn,
                      donate_argnums=(subj.state_argnum,)).lower(*subj.args)
    n_alias = lowered.as_text().count("tf.aliasing_output")
    n_leaves = len(jax.tree_util.tree_leaves(subj.args[subj.state_argnum]))
    n_live = _live_state_leaves(subj)
    if n_alias >= n_live:
        return []
    return [Finding(
        checker="jaxpr", rule="JX003", location=subj.name,
        message=f"donation not fully applied: {n_alias} buffer aliases in "
                f"the lowering for {n_live} live donated state leaves "
                f"({n_leaves} total) — "
                f"{n_live - n_alias} state buffer(s) double-allocated",
        data={"subject": subj.name, "aliases": n_alias,
              "live_state_leaves": n_live, "state_leaves": n_leaves})]


def _live_state_leaves(subj: AuditSubject) -> int:
    """State leaves whose *input* value the round actually reads.

    A donated buffer can only be aliased if its input is used; a leaf the
    round fully overwrites without reading (the per-round quality buffers)
    is dead on entry, gets DCE'd, and legitimately cannot alias. Count the
    state invars that survive into the traced jaxpr's equations/outputs.
    """
    import jax.extend.core as jcore
    jaxpr = jax.make_jaxpr(subj.fn)(*subj.args).jaxpr
    sizes = [len(jax.tree_util.tree_leaves(a)) for a in subj.args]
    start = sum(sizes[:subj.state_argnum])
    state_vars = jaxpr.invars[start:start + sizes[subj.state_argnum]]
    used = set()
    for eqn in jaxpr.eqns:
        used.update(id(v) for v in eqn.invars
                    if not isinstance(v, jcore.Literal))
    used.update(id(v) for v in jaxpr.outvars
                if not isinstance(v, jcore.Literal))
    return sum(1 for v in state_vars if id(v) in used)


def audit_cross_variant_dtypes(subjects: Sequence[AuditSubject]
                               ) -> List[Finding]:
    """JX004: same-named state leaves agree on dtype/weak_type across
    variants (chain vs tree vs quant vs engine-shaped).

    Variants legitimately differ in *shape* (tree slack vs chain slack) and
    in which leaves exist (d_cache vs h_feat, qual); what must not differ is
    the scalar type of a shared leaf — host code reads these leaves
    uniformly, and a weak-type flip is exactly the drift that forks caches
    when states are built by different code paths. Subjects are compared
    within their ``dtype_group`` (the int8-KV variant intentionally stores
    int8 caches and gets its own group).
    """
    seen: Dict[Tuple[str, str], Dict[str, Tuple]] = {}
    for subj in subjects:
        out = jax.eval_shape(subj.fn, *subj.args)
        out_state = out[0] if isinstance(out, tuple) else out
        for key, (shape, dtype, weak) in _aval_map(out_state).items():
            seen.setdefault((subj.dtype_group, key),
                            {})[subj.name] = (dtype, weak)
    findings = []
    for (group, key), per_subj in sorted(seen.items()):
        kinds = set(per_subj.values())
        if len(kinds) > 1:
            detail = ", ".join(f"{s}: {dt}{' (weak)' if wt else ''}"
                               for s, (dt, wt) in sorted(per_subj.items()))
            findings.append(Finding(
                checker="jaxpr", rule="JX004", location=key,
                message=f"state leaf {key} dtype drifts across round "
                        f"variants ({detail})",
                data={"leaf": key,
                      "variants": {s: {"dtype": str(dt), "weak_type": wt}
                                   for s, (dt, wt) in per_subj.items()}}))
    return findings


# ------------------------------------------------------------- subjects

def _tiny_models():
    from ..configs.base import ModelConfig
    from ..models import Model
    base = dict(d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                vocab_size=64, attn_chunk=8, remat=False)
    t = Model(ModelConfig(name="t", arch_type="dense", num_layers=2, **base))
    d = Model(ModelConfig(name="d", arch_type="dense", num_layers=1, **base))
    return t, d


def build_audit_subjects(include_engine: bool = True) -> List[AuditSubject]:
    """Tiny-model instances of every production round variant.

    Model params and prefill states are built *abstractly* where possible
    (``jax.eval_shape``), so the audit never runs a forward pass; the
    engine-shaped subject reuses a real (tiny) ``ContinuousEngine`` state to
    get the paged page-table layout exactly as production builds it.
    """
    from ..core.speculative import SDConfig, _prefill_state, bind_round
    from ..spectree.tree import TreeSpec

    t, d = _tiny_models()
    key = jax.random.PRNGKey(0)
    tp = jax.eval_shape(lambda k: t.init(k)[0], key)
    dp = jax.eval_shape(lambda k: d.init(k)[0], key)
    prompt = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    subjects: List[AuditSubject] = []

    def state_for(sdc, max_total=32):
        return jax.eval_shape(
            partial(_prefill_state, d, t, max_total=max_total, sdc=sdc),
            dp, tp, prompt, key=key)

    chain = SDConfig(gamma=2, temperature=0.0)
    subjects.append(AuditSubject(
        name="chain_round", fn=bind_round(d, t, chain),
        args=(dp, tp, state_for(chain), key)))

    quant = SDConfig(gamma=2, temperature=0.0, kv_quant=True)
    subjects.append(AuditSubject(
        name="chain_round_kv_quant", fn=bind_round(d, t, quant),
        args=(dp, tp, state_for(quant), key), dtype_group="kv_int8"))

    qual = SDConfig(gamma=2, temperature=0.0, quality=True)
    from ..core.speculative import init_quality_buffer
    st_q = dict(state_for(qual))
    st_q["qual"] = jax.eval_shape(partial(init_quality_buffer, 2, qual.gamma))
    subjects.append(AuditSubject(
        name="chain_round_quality", fn=bind_round(d, t, qual),
        args=(dp, tp, st_q, key)))

    tree = TreeSpec((2, 1))
    subjects.append(AuditSubject(
        name="tree_round", fn=bind_round(d, t, chain, tree),
        args=(dp, tp, state_for(chain, max_total=40), key)))

    if include_engine:
        subjects.extend(build_engine_subjects())
    return subjects


def build_engine_subjects() -> List[AuditSubject]:
    """Engine-shaped subjects: the decode round over the *paged* state the
    continuous engine actually feeds it (active mask + page table + pooled
    caches), chain and tree. Built from a real tiny engine so the state
    layout can never drift from production."""
    from ..core.speculative import SDConfig, bind_round
    from ..serving.continuous import ContinuousEngine
    from ..spectree.tree import TreeSpec

    t, d = _tiny_models()
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    sdc = SDConfig(gamma=2, temperature=0.0)
    subjects = []
    for name, tree in (("engine_chain_round", None),
                       ("engine_tree_round", TreeSpec((2, 1)))):
        eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                               draft_params=dp, sd=sdc, tree=tree,
                               max_batch=2, max_seq_len=48, page_size=8)
        fn = bind_round(d, t, eng.sd, tree)
        args = (dp, tp,
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    eng._state),
                key)
        subjects.append(AuditSubject(name=name, fn=fn, args=args))
    return subjects


# --------------------------------------------------------------- driver

def run_jaxpr_audit(subjects: Optional[Sequence[AuditSubject]] = None
                    ) -> FindingSet:
    """Apply every jaxpr rule to every subject; returns all findings."""
    if subjects is None:
        subjects = build_audit_subjects()
    fs = FindingSet()
    for subj in subjects:
        fs.extend(audit_forbidden_primitives(subj))
        fs.extend(audit_state_aval_stability(subj))
        fs.extend(audit_donation(subj))
    fs.extend(audit_cross_variant_dtypes(subjects))
    return fs
