"""Scope and host-span reduction (lib/scopes.py) on a small recorded trace:
a real ``.xplane.pb`` written from a text XSpace."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import scopes, spec  # noqa: E402

MS = 10 ** 9          # picoseconds per millisecond

# the window is [0, 10) ms. Rounds ("jit__unknown(13)", each followed by the
# window gather) at [1, 5) and [8, 9); a third at [9.5, 12) runs past the
# window and is not counted; a prefill step at [6, 7) lies outside them.
MODULES = [("jit__unknown(13)", 1, 5), ("jit__window_gather(5)", 5, 5.1),
           ("jit__unknown(12)", 6, 7),
           ("jit__unknown(13)", 8, 9), ("jit__window_gather(5)", 9, 9.1),
           ("jit__unknown(13)", 9.5, 12)]
R = "jit(_unknown)/"
# the layer scan's while nests two scoped fusions; only leaves count
OPS = [("while.1", 1, 4, R + "verify/while"),
       ("fusion.1", 1.1, 2.1, R + "verify/while/body/attention/kv_gather/gather"),
       ("fusion.2", 2.2, 3.9, R + "verify/while/body/mlp/dot_general"),
       ("fusion.3", 4, 4.5, R + "draft/lm_head/dot_general"),
       ("fusion.4", 4.5, 5, R + "commit/add"),
       ("gather.1", 5, 5.1, "jit(_window_gather)/gather"),
       ("fusion.5", 6, 7, R + "attention/kv_gather/gather"),
       ("fusion.6", 8, 8.6, R + "draft/while/body/attention/kv_gather/gather"),
       ("fusion.7", 8.6, 9, R + "verify/sampling/exp"),
       ("fusion.8", 9.5, 11, R + "verify/while")]
HOST = [("bench_window", 0, 10),
        ("engine_step", 0.5, 5.6), ("decode_round", 0.6, 5.5),
        ("round_wait", 4.8, 5.3),
        ("engine_step", 5.8, 9.2), ("prefill_wait", 6.5, 7.2),
        ("decode_round", 7.5, 9.1), ("round_wait", 8.5, 9.05),
        ("$builtins isinstance", 2, 2.1)]


def _line(lid, name, events, meta):
    """A line of events [(name, start ms, end ms[, scope])]; as on a TPU,
    an op's scope is a stat of its event metadata."""
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev in events:
        name, a, b = ev[:3]
        mid, _ = meta.setdefault(name, (len(meta) + 1, ev[3:]))
        out.append(f"  events {{ metadata_id: {mid} offset_ps: {int(a * MS)}"
                   f" duration_ps: {int((b - a) * MS)} }}")
    out.append("}")
    return out


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        body += _line(lid, lname, events, meta)
    for n, (m, scope) in meta.items():
        stats = "".join(f' stats {{ metadata_id: 1 str_value: "{v}" }}'
                        for v in scope)
        body.append(f'event_metadata {{ key: {m} value {{ id: {m} '
                    f'name: "{n}"{stats} }} }}')
    body.append('stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
    return [f'planes {{ id: {pid} name: "{name}"'] + body + ["}"]


def _write(tmp_path, device=True):
    from jax.profiler import ProfileData
    planes = _plane(1, "/host:CPU", [("python", HOST)])
    if device:
        planes += _plane(2, "/device:TPU:0", [("XLA Modules", MODULES),
                                              ("XLA Ops", OPS)])
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
    return tmp_path


@pytest.fixture
def traced(tmp_path):
    return scopes.load(_write(tmp_path))


def test_leaf_ops_drop_the_scan_around_its_body():
    leaves = scopes.leaf_ops([(1, 4, "while"), (1.1, 2.1, "a"),
                              (2.2, 3.9, "b"), (4, 5, "c")])
    assert [s for _, _, s in leaves] == ["a", "b", "c"]


def test_round_ops_are_leaves_inside_counted_rounds(traced):
    assert traced.n_rounds == 2
    assert sorted(s.split("/")[1] for _, _, s in traced.round_ops) == [
        "commit", "draft", "draft", "verify", "verify", "verify"]


@pytest.mark.parametrize("names,ms", [
    (("draft",), (0.5 + 0.6) / 2),
    (("verify",), (1.0 + 1.7 + 0.4) / 2),     # not the while's 3.0
    (("commit",), 0.5 / 2),
    (("kv_gather",), (1.0 + 0.6) / 2),        # not the prefill's gather
    (("lm_head", "sampling"), (0.5 + 0.4) / 2),
    (("sdpa",), 0.0),                         # scoped program, none here
])
def test_round_scope_ms_per_round(traced, names, ms):
    assert scopes.round_scope_ms(traced, *names) == pytest.approx(ms)


def test_idle_is_intersected_with_the_step_outside_the_waits(traced):
    # device idle in the window: [0, 1), [5.1, 6), [7, 8), [9, 9.5); in
    # engine_step and outside the waits: [0.5, 1), [5.3, 5.6), [5.8, 6),
    # [7.2, 8), [9.05, 9.2) = 1.95 ms over 2 decode rounds
    assert scopes.round_idle_ms(traced) == pytest.approx(1.95 / 2)
    split = scopes.idle_by_span(traced)
    assert split["engine_step"] == pytest.approx(0.8e-3)
    assert split["decode_round"] == pytest.approx(1.15e-3)


def test_none_without_a_tpu_plane_scopes_or_spans(tmp_path):
    assert scopes.load(_write(tmp_path, device=False)) is None
    assert scopes.load(tmp_path / "empty") is None
    assert scopes.round_scope_ms(None, "draft") is None
    assert scopes.round_idle_ms(None) is None
    bare = scopes.reduce_events([(a, b, "") for _, a, b, _ in OPS], MODULES,
                                [h for h in HOST if h[0] == "bench_window"])
    assert scopes.round_scope_ms(bare, "draft") is None   # older program
    assert scopes.round_idle_ms(bare) is None              # no engine spans


@pytest.mark.parametrize("name,value", [
    ("round_draft_ms", 0.55), ("round_verify_ms", 1.55),
    ("round_kv_gather_ms", 0.8), ("round_head_ms", 0.45),
    ("round_idle_ms", 0.975)])
def test_metric_readers(tmp_path, monkeypatch, name, value):
    monkeypatch.setattr(scopes, "TRACE_DIR", _write(tmp_path))
    read = spec.metric_reader(name)
    assert read(SimpleNamespace(trace=object())) == pytest.approx(value)
    assert read(SimpleNamespace(trace=None)) is None
