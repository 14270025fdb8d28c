"""Named scopes of the device ops, and the engine's host spans, from the
traced run's ``.xplane.pb`` (under ``bench/.trace``, still on disk while the
per-layer metrics are read). One parse per file, memoised.

  device ops   line "XLA Ops" of the first "/device:TPU:<n>" plane. An op's
               scope path is the op_name XLA keeps from ``jax.named_scope``
               (stat ``SCOPE_STAT`` of its event metadata). The line nests
               each layer scan's ``while`` around the ops of its body, so
               device time goes to leaf ops only: ops with no other op
               inside their interval.
  rounds       the executions of the round program (``tracing.
               round_program``) wholly inside the window; a metric of the
               round sums the leaf ops inside them and divides by their
               count, as ``round_ms`` does.
  host spans   the engine's spans (``ENGINE_SPANS``), which its tracer puts
               on the host plane by ``jax.profiler.TraceAnnotation``.

A program without the scopes or the spans (an older engine) gives None,
not 0: nothing was there to read.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Tuple

from . import tracing

TRACE_DIR = Path(__file__).resolve().parents[1] / ".trace"
#: the round's phase scopes (core/speculative.py, spectree/round.py); the
#: model step's are attention, kv_write, kv_gather, sdpa, mlp (models/),
#: lm_head and sampling (core/sampling.py)
PHASES = ("draft", "verify", "commit")
#: the engine's host spans (serving/continuous.py)
ENGINE_SPANS = ("engine_step", "admit", "prefill_chunk", "prefill_wait",
                "decode_round", "round_dispatch", "round_wait", "round_emit",
                "retire", "telemetry", "gc")
#: the stat of a TPU op's event metadata that holds its scope path, e.g.
#: ``jit(<unknown>)/verify/while/body/closed_call/attention/kv_gather/
#: gather:`` (``long_name`` holds the HLO instruction)
SCOPE_STAT = "tf_op"


@dataclass
class Scoped:
    window: Tuple[float, float]                  # seconds, trace clock
    ops: List[Tuple[float, float]]               # every op, first device
    round_ops: List[Tuple[float, float, str]]    # leaf ops in the rounds
    n_rounds: int                                # round executions counted
    host: List[Tuple[float, float, str]]         # engine spans in the window


def leaf_ops(events):
    """The events of one line with no other event inside their interval.
    events: [(start, end, scope)] -> the leaves, by start."""
    order = sorted(events, key=lambda e: (e[0], -e[1]))
    parent = [False] * len(order)
    stack = []                                   # indices of open events
    for i, (a, b, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def reduce_events(ops, modules, host_events) -> Scoped:
    """ops: [(start, end, scope path)] of one device's "XLA Ops" line;
    modules: [(name, start, end)] of its "XLA Modules" line; host_events:
    [(name, start, end)] with the ``bench_window`` span. Seconds."""
    t = tracing.reduce_events({"dev": {"ops": [(s, a, b) for a, b, s in ops],
                                       "modules": modules}}, host_events)
    r = tracing.round_program(t)
    rounds = sorted((a, b) for n, a, b in t.modules if n == r)
    starts = [a for a, _ in rounds]
    inside = []
    for a, b, scope in leaf_ops(ops):
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= rounds[i][1]:
            inside.append((a, b, scope))
    lo, hi = t.window
    host = [(a, b, n) for n, a, b in host_events
            if n in ENGINE_SPANS and b > lo and a < hi]
    return Scoped(t.window, [(a, b) for a, b, _ in ops], inside, len(rounds),
                  host)


#: the fields of tsl/profiler/protobuf/xplane.proto read here; a map is a
#: repeated (key = 1, value = 2) entry on the wire
_FIELDS = {
    "XSpace": [(1, "planes", "XPlane")],
    "XPlane": [(2, "name", "string"), (3, "lines", "XLine"),
               (4, "event_metadata", "EventMetadataEntry"),
               (5, "stat_metadata", "StatMetadataEntry")],
    "EventMetadataEntry": [(1, "key", "int64"), (2, "value", "XEventMetadata")],
    "StatMetadataEntry": [(1, "key", "int64"), (2, "value", "XStatMetadata")],
    "XLine": [(2, "name", "string"), (3, "timestamp_ns", "int64"),
              (4, "events", "XEvent")],
    "XEvent": [(1, "metadata_id", "int64"), (2, "offset_ps", "int64"),
               (3, "duration_ps", "int64"), (4, "stats", "XStat")],
    "XStat": [(1, "metadata_id", "int64"), (5, "str_value", "string"),
              (7, "ref_value", "uint64")],
    "XEventMetadata": [(2, "name", "string"), (5, "stats", "XStat")],
    "XStatMetadata": [(2, "name", "string")],
}
_REPEATED = {"planes", "lines", "event_metadata", "stat_metadata", "events",
             "stats"}


@functools.lru_cache(maxsize=1)
def _xspace():
    """The XSpace message class. ``jax.profiler.ProfileData`` gives an
    event only its own stats, and a TPU op keeps ``tf_op`` in its event
    metadata's."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = f.message_type.add(name=msg)
        for num, name, typ in fields:
            fd = m.field.add(name=name, number=num, label=(
                F.LABEL_REPEATED if name in _REPEATED else F.LABEL_OPTIONAL))
            if typ[0].isupper():
                fd.type, fd.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                fd.type = getattr(F, "TYPE_" + typ.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _events(plane, line_names):
    """[(name, start, end, stats)] of the plane's lines ``line_names``,
    seconds; an event's own stats override its metadata's."""
    stat_name = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {e.key: e.value for e in plane.event_metadata}

    def stats(xs):
        return {stat_name.get(x.metadata_id): stat_name.get(x.ref_value)
                if x.ref_value else x.str_value for x in xs}
    out = []
    for line in plane.lines:
        if line.name not in line_names:
            continue
        t0 = line.timestamp_ns * 1000
        for e in line.events:
            m = meta.get(e.metadata_id)
            st = stats(m.stats) if m is not None else {}
            st.update(stats(e.stats))
            # whole picoseconds until here, so back-to-back ops meet exactly
            a = t0 + e.offset_ps
            out.append((m.name if m is not None else "", a * 1e-12,
                        (a + e.duration_ps) * 1e-12, st))
    return out


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int, size: int) -> Optional[Scoped]:
    xs = _xspace()()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    tpus = sorted((p for p in xs.planes if p.name.startswith("/device:TPU")),
                  key=lambda p: p.name)
    if not tpus:
        return None
    ops = [(a, b, st.get(SCOPE_STAT, ""))
           for _, a, b, st in _events(tpus[0], {"XLA Ops"})]
    modules = [(n, a, b) for n, a, b, _ in _events(tpus[0], {"XLA Modules"})]
    wanted = set(ENGINE_SPANS) | {tracing.WINDOW_SPAN}
    host = []
    for p in xs.planes:
        if p.name == "/host:CPU":
            lines = {line.name for line in p.lines}
            host += [(n, a, b) for n, a, b, _ in _events(p, lines)
                     if n in wanted]
    try:
        return reduce_events(ops, modules, host)
    except ValueError:                           # no window span
        return None


def load(trace_dir=None) -> Optional[Scoped]:
    """The parse of the one ``.xplane.pb`` under ``trace_dir`` (default
    ``TRACE_DIR``); None if there is none, or it has no TPU plane or no
    window."""
    trace_dir = TRACE_DIR if trace_dir is None else trace_dir
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        return None
    st = os.stat(paths[0])
    return _parse(paths[0], st.st_mtime_ns, st.st_size)


def _segments(scope: str):
    return {seg.split(":")[0] for seg in scope.split("/")}


def round_scope_ms(s: Optional[Scoped], *names) -> Optional[float]:
    """Device ms per round of the leaf ops under any of ``names``; None
    without rounds, or if no round op carries a phase scope."""
    if s is None or not s.n_rounds:
        return None
    segs = [(b - a, _segments(scope)) for a, b, scope in s.round_ops]
    if not any(seg & set(PHASES) for _, seg in segs):
        return None
    return 1e3 * sum(d for d, seg in segs if seg & set(names)) / s.n_rounds


def _intersect(gaps, spans):
    """Pieces of ``gaps`` that fall inside ``spans`` (both sorted lists of
    disjoint (start, end))."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            lo, hi = max(a, spans[k][0]), min(b, spans[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _spans(s: Scoped, *names):
    return _merge((a, b) for a, b, n in s.host if n in names)


def step_idle(s: Scoped):
    """Device-idle pieces inside ``engine_step`` spans and outside
    ``round_wait`` / ``prefill_wait``, cut exactly at the span edges."""
    lo, hi = s.window
    idle = tracing.idle_intervals(s.ops, lo, hi)
    inside = _intersect(idle, _spans(s, "engine_step"))
    waits = _spans(s, "round_wait", "prefill_wait")
    outside = tracing.idle_intervals(waits, lo, hi)
    return _intersect(inside, outside)


def round_idle_ms(s: Optional[Scoped]) -> Optional[float]:
    """``step_idle`` ms per ``decode_round`` span that starts in the window;
    None without engine spans."""
    if s is None:
        return None
    lo, hi = s.window
    rounds = sum(1 for a, _, n in s.host if n == "decode_round"
                 and lo <= a < hi)
    if not rounds or not _spans(s, "engine_step"):
        return None
    return 1e3 * sum(b - a for a, b in step_idle(s)) / rounds


def idle_by_span(s: Scoped) -> dict:
    """Seconds of ``step_idle`` by the innermost engine span covering each
    part of it."""
    out = {}
    for a, b in step_idle(s):
        cuts = sorted({a, b} | {x for sa, sb, _ in s.host for x in (sa, sb)
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [(sb - sa, n) for sa, sb, n in s.host
                     if sa <= x and y <= sb]
            name = min(cover)[1] if cover else "none"
            out[name] = out.get(name, 0.0) + (y - x)
    return out
