"""Drive the served path (``ContinuousEngine.submit`` / ``step``) through a
traffic mix, and record what a client sees.

The load comes from this one thread: requests are submitted when due,
between engine steps, so a step that runs long delays the submissions
behind it, and latency is timed from the scheduled arrival. How late the
generator ran is recorded.

For the correctness replay, every token the engine streams is kept as a
``Production`` with the key its draws used: the engine splits its key once
per finished prefill (the first token) and once per decode round, in that
order within a step, so the key before each step gives both.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import numpy as np

from .replay import Production, Sample


class EngineTracer:
    """The engine's duck-typed ``tracer=``: records slot assignment and
    counts spans; with ``annotate`` it also mirrors the engine's spans into
    ``jax.profiler.TraceAnnotation`` so that the device trace can attribute
    idle gaps to what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.enabled = annotate
        self.slot: Dict[int, int] = {}
        self.spans: Dict[str, int] = defaultdict(int)

    def span(self, name, **args):
        self.spans[name] += 1
        if self.enabled:
            return jax.profiler.TraceAnnotation(name)
        return nullcontext()

    def async_instant(self, name, aid, ts=None, **args):
        if name == "admit":
            self.slot[aid] = args["slot"]

    def async_begin(self, *a, **k):
        pass

    def async_end(self, *a, **k):
        pass

    def counter(self, *a, **k):
        pass


@dataclass
class Record:
    """What one run of the window saw. Times are seconds on the harness
    clock, whose zero is the start of the traffic."""
    win_start: float
    win_end: float
    sent: Dict[int, float] = field(default_factory=dict)     # due time
    late: Dict[int, float] = field(default_factory=dict)     # submit - due
    first: Dict[int, float] = field(default_factory=dict)
    finish: Dict[int, float] = field(default_factory=dict)
    n_new: Dict[int, int] = field(default_factory=dict)
    counted: List[int] = field(default_factory=list)
    window_tokens: int = 0
    # per step: (key before the step, a prefill finished in it)
    steps: List[tuple] = field(default_factory=list)
    # per request: [(step index, kind, tokens)]
    prods: Dict[int, list] = field(default_factory=lambda: defaultdict(list))
    # per decode round: (t_end, [(rid, context length)])
    rounds: List[tuple] = field(default_factory=list)
    drain_end: float = 0.0

    def sample(self, rid: int, prompt, slot: int) -> Sample:
        s = Sample(rid, slot, np.asarray(prompt))
        for step_i, kind, toks in self.prods[rid]:
            key0, prefilled = self.steps[step_i]
            if kind == "prefill":
                key = jax.random.split(key0)[1]
            else:
                k = jax.random.split(key0)[0] if prefilled else key0
                key = jax.random.split(k)[1]
            s.productions.append(Production(kind, key, list(toks)))
        if s.productions:
            s.productions[-1].complete = False   # may be cut at max_new
        return s


def run_window(engine, reqs, traffic: dict, seconds: float,
               tracer: EngineTracer, on_tick=None) -> Record:
    """Serve ``reqs`` through ``engine``: ramp, window of ``seconds``, then
    drain the counted requests up to ``traffic['drain_cap_s']``. Open and
    closed loops go on sending through the drain, uncounted."""
    from repro.serving import ServeRequest

    loop, ramp = traffic["loop"], traffic["ramp_s"]
    rec = Record(win_start=ramp, win_end=ramp + seconds)
    prompt_len = {r.rid: len(r.prompt) for r in reqs}
    emitted = defaultdict(int)
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    def send(r, due):
        engine.submit(ServeRequest(prompt=r.prompt, max_new_tokens=r.max_new,
                                   request_id=r.rid))
        rec.sent[r.rid] = due
        rec.late[r.rid] = now() - due

    pending = list(reqs)          # not yet sent, in send order
    if loop == "backlog":
        for r in pending:
            send(r, 0.0)
        pending = []
    elif loop == "closed":
        for _ in range(traffic["clients"]):
            send(pending.pop(0), 0.0)
    free_clients = 0
    cap = rec.win_end + traffic["drain_cap_s"]
    while True:
        t = now()
        if loop == "open":
            while pending and pending[0].arrival_s <= t:
                r = pending.pop(0)
                send(r, r.arrival_s)
        elif loop == "closed":
            while free_clients and pending:
                free_clients -= 1
                send(pending.pop(0), t)
        if t >= rec.win_end and (t >= cap or all(
                rid in rec.finish for rid in _counted(loop, rec))):
            break
        if on_tick is not None:
            on_tick(t, rec)
        key0 = engine._key
        n_rounds = tracer.spans["decode_round"]
        with tracer.span("step"):
            events = engine.step()
        te = now()
        step_i = len(rec.steps)
        prefilled = False
        round_rows = []
        for ev in events:
            if ev[0] == "token":
                rid, toks = ev[1], [int(x) for x in ev[2]]
                if rid not in rec.first:
                    rec.first[rid] = te
                    kind, prefilled = "prefill", True
                else:
                    kind = "round"
                    round_rows.append((rid, prompt_len[rid] + emitted[rid]))
                emitted[rid] += len(toks)
                rec.prods[rid].append((step_i, kind, toks))
                if rec.win_start <= te < rec.win_end:
                    rec.window_tokens += len(toks)
            elif ev[0] == "finish":
                rid = ev[1]
                rec.finish[rid] = te
                rec.n_new[rid] = len(ev[2].tokens)
                if loop == "closed":
                    free_clients += 1
        rec.steps.append((key0, prefilled))
        if tracer.spans["decode_round"] > n_rounds:
            rec.rounds.append((te, round_rows))
    rec.drain_end = now()
    rec.counted = _counted(loop, rec)
    return rec


def _counted(loop, rec: Record) -> List[int]:
    """Requests a run is judged on: those sent in the window (open and
    closed loops), or whose first token came in it (backlog)."""
    if loop == "backlog":
        return [rid for rid, t in rec.first.items()
                if rec.win_start <= t < rec.win_end]
    return [rid for rid, t in rec.sent.items()
            if rec.win_start <= t < rec.win_end]
