"""Observability layer (repro.obs): span tracer, metrics registry, phase
timer, roofline attribution, bench persistence, and the engine integration —
trace-reconstructed latencies must match RequestStats, and the phased decode
path must be token-identical to the fused round it decomposes."""
import gc
import json
import time

import jax
import numpy as np
import pytest

from benchmarks.bench_persist import (append_run, compare_run, load_history,
                                      metric_direction, record)
from repro.configs.base import ModelConfig
from repro.core.speculative import SDConfig
from repro.models import Model
from repro.obs import (Histogram, MetricsRegistry, PhaseTimer, Tracer,
                       attribution_report, format_attribution)
from repro.serving import ContinuousEngine, ServeRequest
from repro.spectree import TreeSpec

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
            attn_chunk=16, remat=False)


# -------------------------------------------------------------------- tracer

def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner"):
            time.sleep(0.001)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # child exits first
    inner, outer = evs
    assert inner["ph"] == outer["ph"] == "X"
    # containment: the inner span lies within the outer span's interval
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"step": 1}
    assert inner["dur"] >= 1e3          # slept >= 1ms, exported in us


def test_trace_json_roundtrip(tmp_path):
    tr = Tracer()
    tr.async_begin("request", 7, ts=1.0, prompt_tokens=5)
    tr.async_instant("first_token", 7, ts=1.5)
    tr.async_end("request", 7, ts=2.0, new_tokens=3)
    tr.counter("queue_depth", 2, ts=1.2)
    tr.instant("compact", ts=1.3)
    with tr.span("decode_round"):
        pass
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert len(evs) == 6
    for e in evs:
        assert {"ph", "name", "pid", "ts"} <= set(e)
        assert e["ts"] >= 0.0           # relative to the earliest event
    per_req = [e for e in evs if e["ph"] in ("b", "n", "e")]
    assert [e["ph"] for e in per_req] == ["b", "n", "e"]
    assert all(e["id"] == 7 and e["cat"] == "request" for e in per_req)
    # the async track's own clocks survive the origin shift: 0.5s apart
    assert per_req[1]["ts"] - per_req[0]["ts"] == pytest.approx(0.5e6)
    assert [e for e in evs if e["ph"] == "X"][0]["dur"] >= 0.0


def test_disabled_tracer_is_free():
    tr = Tracer(enabled=False)
    assert tr.span("a") is tr.span("b")     # shared no-op singleton
    tr.async_begin("request", 1)
    tr.counter("x", 1)
    t0 = time.perf_counter()
    for _ in range(100_000):
        with tr.span("hot"):
            pass
    assert time.perf_counter() - t0 < 0.5   # ~no overhead at 100k spans
    assert tr.events() == []


def test_annotated_tracer_enters_a_trace_annotation_per_span(monkeypatch):
    """``annotate`` puts every span, under its exact name, on the jax
    profiler's clock; a plain tracer does not touch the profiler."""
    log = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tr = Tracer(annotate=True)
    with tr.span("decode_round", slot=1):
        with tr.span("round_wait"):
            pass
    assert log == [("enter", "decode_round"), ("enter", "round_wait"),
                   ("exit", "round_wait"), ("exit", "decode_round")]
    assert [e["name"] for e in tr.events()] == ["round_wait", "decode_round"]
    log.clear()
    with Tracer().span("decode_round"):
        pass
    with Tracer(enabled=False, annotate=True).span("decode_round"):
        pass
    assert log == []


# ------------------------------------------------------------------ registry

def test_registry_types_and_guards():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "help text")
    assert reg.counter("reqs_total") is c   # same series on re-request
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")             # cross-type reuse is a bug
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        c.inc(-1)
    c.inc(3)
    c.set_total(10)
    c.set_total(5)                          # monotonic: never lowers
    assert c.value == 10
    g = reg.gauge("depth")
    g.set(4)
    g.inc(-2)
    assert g.value == 2
    assert "reqs_total" in reg and "missing" not in reg


def test_histogram_bucket_edges():
    h = Histogram("lat", buckets=(1.0, 2.0, 5.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))
    for v in (1.0, 1.5, 7.0, 0.2):
        h.observe(v)
    # le is inclusive: 1.0 lands in the le=1 bucket, 1.5 in le=2, 7 in +Inf
    assert h.counts == [2, 1, 0, 1]
    cum = h.cumulative()
    assert cum[-1] == (float("inf"), 4)
    assert [c for _, c in cum] == [2, 3, 3, 4]
    assert h.sum == pytest.approx(9.7) and h.count == 4


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3)
    reg.gauge("depth").set(1.5)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0), help="latency")
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert "# HELP reqs_total requests\n# TYPE reqs_total counter\n" in text
    assert "reqs_total 3\n" in text
    assert "# TYPE depth gauge\ndepth 1.5\n" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text
    assert "lat_s_sum 0.55" in text and "lat_s_count 2" in text
    assert text.endswith("\n")


def test_snapshot_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("steps_total").inc()
    path = tmp_path / "m.jsonl"
    reg.write_snapshot(str(path), ts=1.0)
    reg.counter("steps_total").inc()
    reg.write_snapshot(str(path), ts=2.0)
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    recs = [json.loads(ln) for ln in lines]
    assert recs[0]["metrics"]["steps_total"] == 1
    assert recs[1]["metrics"]["steps_total"] == 2
    assert recs[1]["ts"] > recs[0]["ts"]


# --------------------------------------------------------------- phase timer

def test_phase_timer_residual_closure():
    pt = PhaseTimer()
    for _ in range(2):
        pt.add("draft", 0.03)
        pt.add("verify", 0.05)
        pt.add_step(0.1)
    bd = pt.breakdown()
    # host is the residual, so the breakdown sums to total by construction
    assert sum(bd.values()) == pytest.approx(pt.total_s)
    assert bd["host"] == pytest.approx(0.04)
    assert list(bd)[:2] == ["verify", "draft"]      # sorted descending
    assert sum(pt.fractions().values()) == pytest.approx(1.0)
    assert "verify=" in pt.summary() and "host=" in pt.summary()
    assert PhaseTimer().summary() == "phase timing: no steps recorded"


def test_attribution_report_rows():
    tcfg = ModelConfig(name="t", arch_type="dense", num_layers=2, **BASE)
    dcfg = ModelConfig(name="d", arch_type="dense", num_layers=1, **BASE)
    pt = PhaseTimer()
    for _ in range(4):
        pt.add("draft", 0.01)
        pt.add("verify", 0.02)
        pt.add_step(0.04)
    rep = attribution_report(pt, tcfg, dcfg, batch=2, ctx=64, gamma=3,
                             peak_gbps=100.0)
    assert rep["rounds"] == 4
    assert set(rep["phases"]) == {"draft", "verify"}
    for row in rep["phases"].values():
        assert row["modeled_bytes_per_round"] > 0
        assert row["achieved_gbps"] > 0
        assert row["achieved_mbu"] == pytest.approx(
            row["achieved_gbps"] / 100.0)
    assert rep["phases"]["verify"]["measured_s_per_round"] == (
        pytest.approx(0.02))
    assert "GB/s" in format_attribution(rep)
    assert "no timed device phases" in format_attribution(
        attribution_report(PhaseTimer(), tcfg, dcfg, batch=1, ctx=8, gamma=1))


# ----------------------------------------------------------- bench persist

def test_metric_direction_heuristics():
    assert metric_direction("serving_tok_per_s") == 1
    assert metric_direction("spectree_speedup") == 1
    assert metric_direction("prefix_hit_rate") == 1
    assert metric_direction("serving_ttft_p50_ms") == -1
    assert metric_direction("roofline_step_bytes") == -1
    assert metric_direction("serving_section_wall_s") == 0   # harness time
    assert metric_direction("table1_num_layers") == 0        # unknown: no gate


def test_bench_trajectory_and_compare(tmp_path):
    rows = [("serving_tok_per_s", 100.0, ""), ("serving_ttft_p50_ms", 5.0, ""),
            ("serving_note", "text", "skipped"),
            ("serving_section_wall_s", 9.0, "")]
    rec1 = record("serving", rows, wall_s=9.0, config={"quick": True})
    assert "serving_note" not in rec1["metrics"]
    path = append_run(str(tmp_path), rec1)
    assert path.endswith("BENCH_serving.json")
    hist = load_history(str(tmp_path), "serving")
    assert len(hist) == 1 and hist[0]["metrics"]["serving_tok_per_s"] == 100.0

    # regression in both directions: throughput down 40%, latency up 60%
    worse = record("serving", [("serving_tok_per_s", 60.0, ""),
                               ("serving_ttft_p50_ms", 8.0, ""),
                               ("serving_section_wall_s", 99.0, "")],
                   wall_s=99.0, config={"quick": True})
    regs = compare_run(hist, worse, tol=0.25)
    assert {r[0] for r in regs} == {"serving_tok_per_s",
                                    "serving_ttft_p50_ms"}
    # within tolerance / improvements never flag; wall time never gates
    ok = record("serving", [("serving_tok_per_s", 90.0, ""),
                            ("serving_ttft_p50_ms", 4.0, "")],
                wall_s=1.0, config={"quick": True})
    assert compare_run(hist, ok, tol=0.25) == []
    # a different config (quick vs full) is never comparable
    full = record("serving", [("serving_tok_per_s", 1.0, "")],
                  wall_s=1.0, config={"quick": False})
    assert compare_run(hist, full, tol=0.25) == []
    # trajectory appends and survives a round-trip
    append_run(str(tmp_path), worse)
    assert len(load_history(str(tmp_path), "serving")) == 2


# --------------------------------------------------- engine integration

@pytest.fixture(scope="module")
def models():
    tcfg = ModelConfig(name="t", arch_type="dense", num_layers=4, **BASE)
    dcfg = ModelConfig(name="d", arch_type="dense", num_layers=2, **BASE)
    t, d = Model(tcfg), Model(dcfg)
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    return t, d, tp, dp


def _requests(rng, lens, max_new):
    return [ServeRequest(prompt=rng.integers(0, 64, L).astype(np.int32),
                         max_new_tokens=m, request_id=i)
            for i, (L, m) in enumerate(zip(lens, max_new))]


def test_engine_trace_phases_and_fused_equivalence(models):
    """One instrumented continuous run checks the acceptance criteria:
    trace-reconstructed TTFT matches RequestStats within 1ms, the phase
    breakdown covers the full step wall time (host = residual), per-request
    SD wall time is populated, the registry sees the engine's emitters —
    and the fenced phased round commits the same tokens as the fused jit."""
    t, d, tp, dp = models
    lens, max_new = [6, 10, 8], [8, 6, 7]
    sdc = SDConfig(gamma=2, temperature=0.0)
    kw = dict(target=t, target_params=tp, draft=d, draft_params=dp, sd=sdc,
              max_batch=2, max_seq_len=32, page_size=4, prefill_chunk=8)
    fused = ContinuousEngine(**kw).serve(
        _requests(np.random.default_rng(3), lens, max_new))

    tracer, registry = Tracer(), MetricsRegistry()
    eng = ContinuousEngine(**kw, tracer=tracer, registry=registry,
                           time_phases=True)
    for r in _requests(np.random.default_rng(3), lens, max_new):
        eng.submit(r)
    phased = eng.run()

    # phased round == fused round, token for token (greedy)
    for a, b in zip(fused, phased):
        assert a.request_id == b.request_id
        assert np.array_equal(a.tokens, b.tokens), a.request_id

    # trace reconstructs TTFT to within 1ms of the engine's own stats
    evs = tracer.events()
    begin = {e["id"]: e["ts"] for e in evs if e["ph"] == "b"}
    first = {e["id"]: e["ts"] for e in evs
             if e["ph"] == "n" and e["name"] == "first_token"}
    assert set(begin) == set(first) == {0, 1, 2}
    for rid, st in eng.stats.items():
        assert abs((first[rid] - begin[rid]) / 1e6 - st.ttft_s) < 1e-3
    names = {e["name"] for e in evs}
    assert {"request", "admit", "first_token", "decode_round",
            "draft", "verify", "commit", "queue_depth"} <= names

    # phase attribution covers the whole step time (host is the residual)
    bd = eng.phases.breakdown()
    assert eng.phases.total_s > 0
    assert sum(bd.values()) == pytest.approx(eng.phases.total_s, rel=1e-6)
    assert {"draft", "verify", "commit", "prefill"} <= set(bd)
    device_frac = 1.0 - eng.phases.fractions()["host"]
    assert device_frac > 0.5            # fenced phases dominate the step

    # satellite fixes: per-request SD wall time is stamped every round
    for st in eng.stats.values():
        assert st.sd.wall_time_s > 0
        assert st.sd.tokens_per_s() > 0

    # engine emitters landed in the registry
    for name in ("serve_steps_total", "serve_decode_rounds_total",
                 "sched_submitted_total", "sd_tokens_total",
                 "sd_accepted_per_round"):
        assert name in registry, name
    assert registry.counter("serve_completed_total").value == 3
    hist = registry.histogram("sd_accepted_per_round")
    assert hist.count > 0
    total_new = sum(st.new_tokens for st in eng.stats.values())
    assert registry.counter("sd_tokens_total").value == total_new


def test_telemetry_ring_is_bounded(models):
    """The per-step series are bounded rings; the summary aggregates stay
    exact after the ring wraps."""
    from repro.core.metrics import ServingTelemetry
    tel = ServingTelemetry(window=4)
    for i in range(10):
        tel.sample(queue_depth=i, active_rows=2, free_pages=5,
                   shared_frac=0.5)
    assert len(tel.queue_depth) == 4            # ring wrapped
    assert list(tel.queue_depth) == [6, 7, 8, 9]
    assert tel.max_queue_depth == 9             # exact despite eviction
    assert tel.mean_active_rows == pytest.approx(2.0)
    assert tel.mean_shared_frac == pytest.approx(0.5)
    assert tel.steps == 10


def _engine_kw(models, **kw):
    t, d, tp, dp = models
    return dict(target=t, target_params=tp, draft=d, draft_params=dp,
                sd=SDConfig(gamma=2, temperature=0.0), max_batch=2,
                max_seq_len=32, page_size=4, prefill_chunk=8, **kw)


def test_gc_spans_only_while_an_enabled_tracer_lives(models):
    """A disabled tracer installs no gc hook; an enabled one records each
    collection as a ``gc`` span, and its hook goes with the engine."""
    n = len(gc.callbacks)
    ContinuousEngine(**_engine_kw(models))
    ContinuousEngine(**_engine_kw(models, tracer=Tracer(enabled=False)))
    assert len(gc.callbacks) == n
    tr = Tracer()
    eng = ContinuousEngine(**_engine_kw(models, tracer=tr))
    assert len(gc.callbacks) == n + 1
    gc.collect()
    spans = [e for e in tr.events() if e["name"] == "gc"]
    assert spans and spans[-1]["ph"] == "X"
    assert spans[-1]["args"]["generation"] == 2
    assert spans[-1]["args"]["collected"] >= 0
    del eng
    gc.collect()
    assert len(gc.callbacks) == n


def _within(child, parent):
    return (parent["ts"] - 1e-3 <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-3)


def test_engine_spans_nest(models):
    """The round's host spans nest as the profiler reads them: dispatch,
    wait and emit in decode_round, retire in round_emit, the first-token
    wait in prefill_chunk, and everything in engine_step."""
    tr = Tracer()
    eng = ContinuousEngine(**_engine_kw(models, tracer=tr))
    eng.serve(_requests(np.random.default_rng(5), [6, 10, 8], [8, 6, 7]))
    spans = [e for e in tr.events() if e["ph"] == "X" and e["name"] != "gc"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    assert {"engine_step", "admit", "prefill_chunk", "prefill_wait",
            "decode_round", "round_dispatch", "round_wait", "round_emit",
            "retire", "telemetry"} <= set(by)
    assert len(by["retire"]) == 3 and len(by["prefill_wait"]) == 3
    parents = {"round_dispatch": "decode_round", "round_wait": "decode_round",
               "round_emit": "decode_round", "retire": "round_emit",
               "prefill_wait": "prefill_chunk"}
    for child, parent in parents.items():
        for e in by[child]:
            assert any(_within(e, p) for p in by[parent]), (child, e)
    for e in spans:
        if e["name"] != "engine_step":
            assert any(_within(e, p) for p in by["engine_step"]), e
    assert len(by["decode_round"]) == len(by["round_wait"]) \
        == eng.telemetry.decode_rounds


@pytest.mark.parametrize("tree", [None, TreeSpec((2, 1))],
                         ids=["chain", "tree"])
def test_round_hlo_carries_named_scopes(models, tree):
    """The engine's round program names its phases and the model step's
    parts in its HLO metadata, and keeps its program name."""
    eng = ContinuousEngine(**_engine_kw(models, tree=tree))
    txt = eng._round.lower(eng._d_params, eng.target_params, eng._state,
                           jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("draft/", "verify/", "commit", "attention", "kv_write",
                  "kv_gather", "sdpa", "mlp", "lm_head", "sampling"):
        assert scope in txt, scope
    assert "module @jit__unknown" in txt
