"""Device trace of a traced run, and its reduction to numbers.

The capture: ``jax.profiler`` over a few seconds from the middle of the
window, inside a ``TraceAnnotation("bench_window")`` that marks its bounds
on the host line. The reduction reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``:

  device planes   "/device:TPU:<n>", those of the cell's chips; line "XLA
                  Ops" holds one event per device operation, line "XLA
                  Modules" one per execution of a compiled program (named
                  after the jitted function).
  host plane      "/host:CPU"; the engine's spans (admit, prefill_chunk,
                  decode_round, mirrored by ``driver.EngineTracer``) and
                  the harness's span around each ``engine.step()`` (step).

busy    union of the device-op intervals inside the window, per device,
        averaged over devices; idle share = 1 - busy / window.
op time each op name's device seconds inside the window, per chip: the
        mean over the device planes, so a cell across chips reads as one
        of its chips does.
modules each execution of a compiled program, in order; programs are
        named ``jit_<function>(<fingerprint>)``. The engine's jitted
        partials (the round, the prefill steps) all carry the function name
        ``_unknown``, so the round is found by its place: the engine runs
        the window gather (``_window_gather``) right after every round.
        The prefill steps are the other programs with the round's name and
        another fingerprint.
gaps    each idle interval between device ops, labelled by the innermost
        host span that covers its midpoint: an engine span, "step" for the
        engine's other host work, "none" for the harness between steps.
"""
from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("admit", "prefill_chunk", "decode_round", "step")


@dataclass
class Trace:
    window: Tuple[float, float]                  # seconds, trace clock
    ops: Dict[str, List[Tuple[float, float]]]    # device -> [(start, end)]
    op_time: Dict[str, float]                    # op name -> seconds per
                                                 # device, inside window
    modules: List[Tuple[str, float, float]]      # first device, by start
    host: List[Tuple[float, float, str]]         # (start, end, span name)
    planes: List[str] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def union_length(intervals, lo, hi) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        a, b = _clip(a, b, lo, hi)
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def idle_intervals(intervals, lo, hi) -> List[Tuple[float, float]]:
    """The gaps in [lo, hi) that no interval covers."""
    gaps, t = [], lo
    for a, b in sorted(intervals):
        a, b = _clip(a, b, lo, hi)
        if b <= a:
            continue
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def reduce_events(device_lines, host_events) -> Trace:
    """device_lines: {device: {"ops": [(name, start, end)], "modules":
    [(name, start, end)]}}; host_events: [(name, start, end)], seconds.
    The window is the host span ``bench_window``."""
    win = [(a, b) for n, a, b in host_events if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lo, hi = win[0]
    ops, op_time, modules = {}, defaultdict(float), []
    for dev, lines in sorted(device_lines.items()):
        ops[dev] = [(a, b) for _, a, b in lines["ops"]]
        for n, a, b in lines["ops"]:
            a2, b2 = _clip(a, b, lo, hi)
            if b2 > a2:
                op_time[n] += b2 - a2
        if not modules:
            modules = sorted(((n, a, b) for n, a, b in lines["modules"]
                              if lo <= a and b <= hi), key=lambda e: e[1])
    host = [(a, b, n) for n, a, b in host_events
            if n in HOST_SPANS and b > lo and a < hi]
    n = max(len(device_lines), 1)
    return Trace((lo, hi), ops, {k: v / n for k, v in op_time.items()},
                 modules, host)


def _device_id(plane_name: str):
    """n of "/device:TPU:<n>", None for any other plane."""
    head, _, n = plane_name.rpartition(":")
    return int(n) if head == "/device:TPU" and n.isdigit() else None


def load(trace_dir: str, device_ids) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``; of the device
    planes, those of ``device_ids``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    device_lines, host_events, names = {}, [], []
    for plane in pd.planes:
        names.append(plane.name)
        dev = _device_id(plane.name)
        if dev in device_ids:
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if kind:
                    lines[kind] = [(e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events]
            device_lines[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        host_events.append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    t = reduce_events(device_lines, host_events)
    t.planes = names
    return t


def busy_s(t: Trace) -> float:
    """Device-busy seconds in the window, averaged over devices."""
    lo, hi = t.window
    if not t.ops:
        return 0.0
    return sum(union_length(iv, lo, hi) for iv in t.ops.values()) / len(t.ops)


def _base(name: str) -> str:
    return name.split("(")[0]


def round_program(t: Trace):
    """Name of the round's program: the one executed right before the
    window gather, most often; None if the trace shows no round."""
    after = defaultdict(int)
    for (prev, _, _), (n, _, _) in zip(t.modules, t.modules[1:]):
        if "window_gather" in n:
            after[prev] += 1
    return max(after, key=after.get) if after else None


def program_time(t: Trace, match) -> Tuple[float, int]:
    """(device seconds, executions) of the programs ``match(name)``
    accepts, executions wholly inside the window."""
    durs = [b - a for n, a, b in t.modules if match(n)]
    return sum(durs), len(durs)


def round_time(t: Trace) -> Tuple[float, int]:
    r = round_program(t)
    return program_time(t, lambda n: n == r) if r else (0.0, 0)


def prefill_time(t: Trace) -> Tuple[float, int]:
    """Device time of the prefill-step programs: the round's function name
    under another fingerprint."""
    r = round_program(t)
    if r is None:
        return 0.0, 0
    return program_time(t, lambda n: n != r and _base(n) == _base(r))


def host_count(t: Trace, name: str) -> int:
    """Host spans ``name`` that start inside the window."""
    lo, hi = t.window
    return sum(1 for a, b, n in t.host if n == name and lo <= a < hi)


def _label(t: Trace, mid: float) -> str:
    best = None
    for a, b, n in t.host:
        if a <= mid < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, n)
    return best[2] if best else "none"


def breakdown(t: Trace, top: int = 10) -> dict:
    """Device ops that took most time (per chip), and the longest idle gaps
    labelled by the host span they fell in (first device's gaps)."""
    ops = sorted(t.op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if t.ops:
        dev = sorted(t.ops)[0]
        lo, hi = t.window
        gaps = [(_label(t, (a + b) / 2), b - a)
                for a, b in idle_intervals(t.ops[dev], lo, hi)]
        gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
