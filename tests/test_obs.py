"""Observability layer (repro.obs): span tracer, metrics registry, and the
engine integration — trace-reconstructed latencies must match RequestStats,
and the engine's emitters must land in the registry."""
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.speculative import SDConfig
from repro.models import Model
from repro.obs import Histogram, MetricsRegistry, Tracer
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


@pytest.mark.parametrize("tree", [None, TreeSpec((2, 1))],
                         ids=["chain", "tree"])
def test_engine_trace_and_registry(models, tree):
    """One instrumented continuous run, chain and tree: trace-reconstructed
    TTFT matches RequestStats within 1ms, per-request SD wall time is
    populated, and the registry sees the engine's emitters."""
    tracer, registry = Tracer(), MetricsRegistry()
    eng = ContinuousEngine(**_engine_kw(models, tree=tree, tracer=tracer,
                                        registry=registry))
    for r in _requests(np.random.default_rng(3), [6, 10, 8], [8, 6, 7]):
        eng.submit(r)
    eng.run()

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
            "queue_depth"} <= names

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


def _variant_engine(models, variant):
    """A tiny engine of the named kind: chain, tree (``TreeSpec((2, 1))``),
    eagle (an EAGLE head drafter in place of the draft model) or int8
    (int8 K/V pools)."""
    kw = _engine_kw(models)
    if variant == "tree":
        kw["tree"] = TreeSpec((2, 1))
    elif variant == "int8":
        kw["sd"] = SDConfig(gamma=2, temperature=0.0, kv_quant=True)
        kw["kv_quant"] = True
    elif variant == "eagle":
        from repro.draftheads import HeadConfig, HeadDrafter
        h = HeadDrafter(HeadConfig.for_target("eagle", models[0].cfg))
        kw.update(draft=None, draft_params=None, draft_heads=h,
                  draft_head_params=h.init(jax.random.PRNGKey(7)))
    return ContinuousEngine(**kw)


@pytest.mark.parametrize("variant", ["chain", "tree", "eagle", "int8"])
def test_round_hlo_carries_named_scopes(models, variant):
    """The engine's round program names its phases and the model step's
    parts in its HLO metadata, and keeps its program name."""
    eng = _variant_engine(models, variant)
    txt = eng._round.lower(eng._d_params, eng.target_params, eng._state,
                           jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("draft/", "verify/", "commit", "attention", "kv_write",
                  "kv_gather", "sdpa", "mlp", "lm_head", "sampling"):
        assert scope in txt, scope
    assert "module @jit__unknown" in txt


@pytest.mark.parametrize("variant", ["chain", "eagle"])
def test_prefill_step_keeps_the_round_program_name(models, variant):
    """The engine's prefill steps lower to the round's program name under
    another fingerprint, which is how the device trace tells prefill from
    the round: the drafter's and the target's step, and the target's
    hidden-returning step that seeds draft heads."""
    eng = _variant_engine(models, variant)
    C = eng.prefill_chunk
    toks = jnp.zeros((1, C), jnp.int32)
    pos = jnp.arange(C, dtype=jnp.int32)[None]
    table = jnp.asarray(eng._table_h[:1])
    steps = [(eng._t_step, eng.target_params, "t_cache")]
    if eng._d_step is not None:
        steps.append((eng._d_step, eng.draft_params, "d_cache"))
    assert (eng._d_step is None) == (variant == "eagle")
    for step, params, cache in steps:
        txt = step.lower(params, toks, pos, eng._state[cache],
                         page_table=table).as_text()
        assert "module @jit__unknown" in txt, cache
