"""Recompile sentinel: prove the serving engine's jit cache is stable.

The continuous engine's whole design premise (DESIGN.md, PR 2) is that all
shapes the jitted code sees are fixed at construction, so membership churn
never recompiles anything: steady-state traffic runs exactly the programs
the warm-up compiled. A silently widened dtype, a weak-type python scalar,
or a host index array sneaking into a jitted call forks the cache and turns
every round into a compile — the failure mode is pure latency, invisible to
correctness tests. This module watches XLA compiles directly:

  * ``CompileWatcher`` — context manager counting backend compiles via
    jax's monitoring events and recording each compiled program's
    name + global shape signature from the ``jax_log_compiles`` log stream.
  * ``run_recompile_sentinel`` — replays a ``traffic/`` mix through a fresh
    engine twice. Pass 1 (cold) must compile each distinct program
    signature exactly once (compiles == shape buckets, no duplicate
    signatures); pass 2 (steady state: new engine, same configs, same
    stream) must compile NOTHING — the lru-cached jitted rounds and the
    per-shape eager kernels are all warm.
  * ``count_device_gets`` / ``audit_round_transfers`` — the one-host-sync
    contract: a single engine decode round under
    ``jax.transfer_guard("disallow")`` performs exactly one explicit
    ``jax.device_get`` and zero implicit transfers.

Rules
  RC001  duplicate compile signature within one cold pass (same program
         compiled twice -> the jit cache is forked on something)
  RC002  steady-state compile: a warm pass over identical traffic
         compiled a new program
  RC003  decode round performed != 1 ``jax.device_get``
  RC004  implicit host<->device transfer inside a decode round
"""
from __future__ import annotations

import contextlib
import logging
import re
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from .findings import Finding, FindingSet

# the pxla dispatch logger emits "Compiling <name> with global shapes and
# types [...]" at WARNING whenever jax_log_compiles is on; the name may come
# wrapped as "jit(<name>)"
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(r"Compiling (?:jit\()?([^\s()]+)")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# jax-internal housekeeping compiles (eager single-primitive dispatch, PRNG
# helpers, param-init samplers). Their log lines can legitimately repeat for
# identical-looking signatures because the real cache key carries detail the
# message omits (callable identity, static args), so they are not evidence
# of a forked *round* cache — the sentinel's subject is the engine's own
# jitted programs (sd_round / tree_sd_round / prefill / window gather),
# which log under their python function names.
_HOUSEKEEPING_NAMES = frozenset({
    "_threefry_seed", "_threefry_split", "_truncated_normal", "_normal",
    "_uniform", "_gamma", "broadcast_in_dim", "slice", "iota", "copy",
    "convert_element_type", "transpose", "reshape", "concatenate",
    "squeeze", "select_n", "gather", "dynamic_slice", "dynamic_update_slice",
})


def _engine_signatures(signatures):
    return [s for s in signatures
            if _COMPILE_RE.match(s).group(1) not in _HOUSEKEEPING_NAMES]


class _LogCapture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.messages: List[str] = []

    def emit(self, record):
        self.messages.append(record.getMessage())


class CompileWatcher:
    """Count XLA backend compiles and record compiled program signatures.

    ``signatures`` holds one string per ``Compiling <name> with global
    shapes and types ...`` log line — name plus abstract argument shapes,
    i.e. exactly the jit cache key the dispatch missed on. ``n_compiles``
    counts backend-compile monitoring events (includes compiles that bypass
    the dispatch logger, e.g. internal helpers); ``seconds`` sums their
    durations (a persistent-cache hit counts as its read time).
    """

    def __init__(self):
        self.signatures: List[str] = []
        self.n_compiles = 0
        self.seconds = 0.0
        self._handler: Optional[_LogCapture] = None
        self._prev_log_compiles = None
        self._prev_level = None

    def _on_event(self, event: str, duration: float, **kw):
        if event == _COMPILE_EVENT:
            self.n_compiles += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._handler = _LogCapture()
        logger = logging.getLogger(_COMPILE_LOGGER)
        self._prev_level = logger.level
        self._prev_propagate = logger.propagate
        logger.addHandler(self._handler)
        logger.setLevel(logging.DEBUG)
        logger.propagate = False        # capture only; keep stderr clean
        # jax_log_compiles also makes jax._src.dispatch narrate every
        # trace/lower/compile step at WARNING — mute it while watching
        dispatch = logging.getLogger("jax._src.dispatch")
        self._prev_dispatch_level = dispatch.level
        dispatch.setLevel(logging.ERROR)
        self._prev_log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        jax.config.update("jax_log_compiles", self._prev_log_compiles)
        logger = logging.getLogger(_COMPILE_LOGGER)
        logger.removeHandler(self._handler)
        logger.setLevel(self._prev_level)
        logger.propagate = self._prev_propagate
        logging.getLogger("jax._src.dispatch").setLevel(
            self._prev_dispatch_level)
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        self.signatures = [m for m in self._handler.messages
                           if _COMPILE_RE.match(m)]
        return False

    @property
    def names(self) -> List[str]:
        return [_COMPILE_RE.match(s).group(1) for s in self.signatures]

    def duplicate_signatures(self) -> Dict[str, int]:
        seen: Dict[str, int] = {}
        for s in _engine_signatures(self.signatures):
            seen[s] = seen.get(s, 0) + 1
        return {s: n for s, n in seen.items() if n > 1}


@contextlib.contextmanager
def count_device_gets():
    """Count explicit ``jax.device_get`` calls in the block (the engine's
    one-sync-per-round budget). Yields a one-element list holding the count.
    """
    counter = [0]
    real = jax.device_get

    def counted(x):
        counter[0] += 1
        return real(x)

    jax.device_get = counted
    try:
        yield counter
    finally:
        jax.device_get = real


# ----------------------------------------------------------------- engines

def _sentinel_engine(tree=None, prefix_cache=True, max_batch=4):
    """Tiny engine sized for the ``traffic`` mixes (summarize prompts reach
    128 tokens). Same model configs every call, so jitted rounds stay
    lru-cache warm across engines — the property the sentinel certifies."""
    from .jaxpr_audit import _tiny_models
    from ..core.speculative import SDConfig
    from ..serving.continuous import ContinuousEngine

    t, d = _tiny_models()
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    return ContinuousEngine(
        target=t, target_params=tp, draft=d, draft_params=dp,
        sd=SDConfig(gamma=2, temperature=0.0), tree=tree,
        max_batch=max_batch, max_seq_len=144, page_size=16,
        prefix_cache=prefix_cache)


def _mix_requests(mix: str, n_requests: int, seed: int = 0):
    from ..traffic import make_mix
    return make_mix(mix).build(n_requests, rate_per_s=500.0, vocab_size=64,
                               seed=seed)


def run_recompile_sentinel(mix: str = "mixed", n_requests: int = 12
                           ) -> FindingSet:
    """Cold pass compiles each signature once; warm pass compiles nothing.

    Two *fresh* engines (same model/engine configs) replay the identical
    request stream. The first populates the process-wide jit caches — one
    compile per distinct program signature (shape bucket). The second is
    steady state: any compile it triggers is a recompile production would
    pay per-engine (or worse, per-round) and is reported with the exact
    program signature that missed.
    """
    fs = FindingSet()
    with CompileWatcher() as cold:
        _sentinel_engine().serve(_mix_requests(mix, n_requests))
    for sig, n in sorted(cold.duplicate_signatures().items()):
        fs.add(Finding(
            checker="recompile", rule="RC001", location=sig.split()[1],
            message=f"cold pass compiled the same program signature {n}x "
                    f"(jit cache forked): {sig[:200]}",
            data={"signature": sig, "count": n, "mix": mix}))
    with CompileWatcher() as warm:
        _sentinel_engine().serve(_mix_requests(mix, n_requests))
    for sig in _engine_signatures(warm.signatures):
        fs.add(Finding(
            checker="recompile", rule="RC002", location=sig.split()[1],
            message=f"steady-state recompile over identical traffic: "
                    f"{sig[:200]}",
            data={"signature": sig, "mix": mix}))
    cold_eng = _engine_signatures(cold.signatures)
    fs.stats = {   # type: ignore[attr-defined]
        "mix": mix, "n_requests": n_requests,
        "cold_signatures": len(cold_eng),
        "cold_buckets": len(set(cold_eng)),
        "cold_housekeeping": len(cold.signatures) - len(cold_eng),
        "cold_backend_compiles": cold.n_compiles,
        "warm_signatures": len(_engine_signatures(warm.signatures)),
        "warm_housekeeping": len(warm.signatures)
        - len(_engine_signatures(warm.signatures)),
        "warm_backend_compiles": warm.n_compiles,
    }
    return fs


def _warm_decode_engine(tree=None):
    """Engine stepped until a decode round has already run (and compiled):
    the transfer audit must observe steady-state rounds, not warm-up."""
    eng = _sentinel_engine(tree=tree, prefix_cache=False, max_batch=2)
    rng = np.random.default_rng(0)
    from ..serving.scheduler import ServeRequest
    for rid in range(2):
        eng.submit(ServeRequest(
            prompt=rng.integers(0, 64, 12).astype(np.int32),
            max_new_tokens=64, request_id=rid))
    for _ in range(32):
        eng.step()
        if eng.telemetry.decode_rounds >= 2:
            return eng
    raise RuntimeError("engine never reached steady decode state")


def audit_round_transfers(tree=None) -> FindingSet:
    """One steady-state decode round under ``transfer_guard('disallow')``:
    exactly one explicit device_get, zero implicit transfers (RC003/RC004).
    """
    fs = FindingSet()
    name = "tree_round" if tree is not None else "chain_round"
    eng = _warm_decode_engine(tree=tree)
    try:
        with jax.transfer_guard("disallow"), count_device_gets() as gets:
            eng._decode_round()
    except Exception as e:   # noqa: BLE001 - guard violations raise runtime errors
        fs.add(Finding(
            checker="recompile", rule="RC004", location=name,
            message=f"implicit host<->device transfer inside a decode round "
                    f"({type(e).__name__}: {str(e)[:200]})",
            data={"round": name, "error": str(e)}))
        return fs
    if gets[0] != 1:
        fs.add(Finding(
            checker="recompile", rule="RC003", location=name,
            message=f"decode round performed {gets[0]} device_get calls; "
                    f"the contract is exactly one host sync per round",
            data={"round": name, "device_gets": gets[0]}))
    return fs
