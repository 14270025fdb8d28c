"""The collective ops of the decode rounds, from the traced run's
``.xplane.pb``: device ops whose HLO instruction is a collective that the
partitioner put into a program across chips (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, and their async
``-start`` / ``-done`` halves). They carry no scope of their own (their
``tf_op`` is the op they were derived from), so they are found by the
instruction: its name in the event metadata (``all-reduce.12``) or the
opcode in the ``long_name`` stat (``%x = bf16[...] all-reduce(...)``).

Read on the first chip, inside the round's executions wholly in the window,
leaf ops only, as ``lib/scopes.py`` reads scopes.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Optional

from . import scopes, tracing

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
_KIND = "(?:" + "|".join(KINDS) + ")(?:-start|-done)?"
#: an instruction name: ``all-reduce.12``, ``%all-gather-start``
_NAME = re.compile(r"^%?" + _KIND + r"(?:[.\-]|$)")
#: the opcode of ``%name = <shape> <opcode>(<operands>)``: the first word
#: after the ``=`` that opens a parenthesis (a shape never does)
_OPCODE = re.compile(r"=.*?(?:^|[\s)])([a-z][a-z0-9\-]*)\(")


def is_collective(name: str, long_name: str = "") -> bool:
    """Is the instruction of an op with this metadata name and long name a
    collective? An operand named after a collective does not count."""
    if _NAME.match(name):
        return True
    m = _OPCODE.search(long_name)
    return bool(m) and re.fullmatch(_KIND, m.group(1)) is not None


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int, size: int):
    xs = scopes._xspace()()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    tpus = sorted((p for p in xs.planes if p.name.startswith("/device:TPU")),
                  key=lambda p: p.name)
    if not tpus:
        return None
    ops = [(a, b, "collective" if is_collective(n, st.get("long_name") or "")
            else "") for n, a, b, st in scopes._events(tpus[0], {"XLA Ops"})]
    modules = [(n, a, b) for n, a, b, _ in
               scopes._events(tpus[0], {"XLA Modules"})]
    host = [(n, a, b) for p in xs.planes if p.name == "/host:CPU"
            for n, a, b, _ in scopes._events(p, {ln.name for ln in p.lines})
            if n == tracing.WINDOW_SPAN]
    try:
        return scopes.reduce_events(ops, modules, host)
    except ValueError:                           # no window span
        return None


def load(trace_dir=None):
    """Rounds of the one ``.xplane.pb`` under ``trace_dir`` (default
    ``scopes.TRACE_DIR``) with each leaf op marked collective or not; None
    without a trace or a TPU plane."""
    trace_dir = scopes.TRACE_DIR if trace_dir is None else trace_dir
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        return None
    st = os.stat(paths[0])
    return _parse(paths[0], st.st_mtime_ns, st.st_size)


def round_collective_ms(s) -> Optional[float]:
    """Device ms per round of the collective leaf ops; None without
    rounds."""
    if s is None or not s.n_rounds:
        return None
    return 1e3 * sum(b - a for a, b, kind in s.round_ops
                     if kind == "collective") / s.n_rounds
