"""Speculation-quality observability (repro.obs.quality/sketch/recorder).

Covers: GK quantile-sketch rank-error bound on adversarial streams (plus a
hypothesis property variant when installed), Page–Hinkley false-positive /
detection behavior, QualityStats accounting semantics (attempted vs drafted
vs accepted), temp-0 token identity of the engine with quality telemetry on
(chain AND tree), SLO burn-rate alerting, the flight recorder, and the
satellite fixes (NaN latency percentiles, histogram bucket validation,
acceptance-attribution report).
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.metrics import latency_percentiles
from repro.core.speculative import SDConfig
from repro.models import Model
from repro.obs import (FlightRecorder, GKSketch, Histogram, PageHinkley,
                       QualityStats, SLOConfig, SLOTracker, acceptance_report,
                       log_buckets)
from repro.serving import ContinuousEngine, ServeRequest
from repro.spectree import TreeSpec

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
            attn_chunk=16, remat=False)


# ------------------------------------------------------------- GK sketch

def _rank_error(stream, sketch, phi):
    """Distance between the returned value's rank span and phi's rank."""
    s = np.sort(np.asarray(stream, np.float64))
    v = sketch.query(phi)
    r = max(1, min(len(s), int(np.ceil(phi * len(s)))))
    lo = int(np.searchsorted(s, v, side="left")) + 1
    hi = int(np.searchsorted(s, v, side="right"))
    if lo <= r <= hi:
        return 0
    return min(abs(lo - r), abs(hi - r))


ADVERSARIAL = {
    "sorted": np.arange(2000, dtype=float),
    "reverse": np.arange(2000, dtype=float)[::-1],
    "duplicates": np.repeat(np.arange(40, dtype=float), 50),
    "sawtooth": np.tile([0.0, 1e6], 1000),
    "random": np.random.default_rng(0).normal(size=2000),
    "heavy_tail": np.random.default_rng(1).pareto(1.2, size=2000),
}


@pytest.mark.parametrize("name", sorted(ADVERSARIAL))
def test_gk_sketch_rank_bound(name):
    stream = ADVERSARIAL[name]
    eps = 0.01
    sk = GKSketch(eps=eps)
    for v in stream:
        sk.insert(v)
    assert sk.n == len(stream)
    # memory stays sublinear (the entire point of sketching)
    assert len(sk) < len(stream) / 4
    for phi in (0.01, 0.25, 0.5, 0.75, 0.9, 0.99):
        err = _rank_error(stream, sk, phi)
        assert err <= eps * len(stream) + 1, \
            f"{name}: phi={phi} rank error {err} > {eps * len(stream)}"


def test_gk_sketch_small_and_empty():
    sk = GKSketch()
    assert np.isnan(sk.query(0.5))
    sk.insert(7.0)
    assert sk.query(0.0) == 7.0 and sk.query(1.0) == 7.0


def test_gk_sketch_hypothesis_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.floats(min_value=-1e9, max_value=1e9,
                              allow_nan=False), min_size=1, max_size=600),
           st.floats(min_value=0.0, max_value=1.0))
    def check(stream, phi):
        eps = 0.02
        sk = GKSketch(eps=eps)
        for v in stream:
            sk.insert(v)
        assert _rank_error(stream, sk, phi) <= eps * len(stream) + 1

    check()


# ---------------------------------------------------------- Page–Hinkley

def test_page_hinkley_no_false_positive_stationary():
    """Default parameterization over stationary binomial acceptance
    fractions (the stream the engine actually feeds it): zero alarms
    across seeds — deterministic, so this pins the FP bound."""
    alarms = 0
    for seed in range(20):
        rng = np.random.default_rng(seed)
        ph = PageHinkley()
        for x in rng.binomial(24, 0.8, 400) / 24.0:
            alarms += ph.update(float(x))
    assert alarms == 0


def test_page_hinkley_detects_sustained_drop():
    ph = PageHinkley()
    rng = np.random.default_rng(5)
    for x in rng.binomial(24, 0.9, 60) / 24.0:
        assert not ph.update(float(x))
    fired_at = None
    for i, x in enumerate(rng.binomial(24, 0.4, 40) / 24.0):
        if ph.update(float(x)):
            fired_at = i
            break
    assert fired_at is not None and fired_at < 10, \
        "a 0.9 -> 0.4 acceptance drop must alarm within a few rounds"


def test_page_hinkley_rearms_after_alarm():
    ph = PageHinkley(min_samples=4)
    for _ in range(10):
        ph.update(0.9)
    for _ in range(10):
        if ph.update(0.1):
            break
    assert ph.alarms == 1
    # new baseline at the post-drop level: staying there is NOT an alarm
    assert not any(ph.update(0.1) for _ in range(20))
    # recovery upward is not an alarm either (one-sided detector) ...
    assert not any(ph.update(0.9) for _ in range(20))
    # ... but a second independent drop from the recovered level fires
    assert any(ph.update(0.1) for _ in range(10))
    assert ph.alarms == 2


# ----------------------------------------------------------- QualityStats

def test_quality_stats_accounting():
    q = QualityStats(depth=3)
    tvd = np.array([[0.1, 0.2, 0.3], [0.4, 0.5, 0.6]])
    ent = np.array([[0.01, 0.7, 3.0], [0.01, 0.01, 5.0]])
    acc = np.array([[True, True, False], [False, False, False]])
    q.update_round(tvd, ent, acc)
    # attempted: row0 reaches all depths, row1 only depth 1
    assert q.attempted.tolist() == [2, 1, 1]
    assert q.accepted.tolist() == [1, 1, 0]
    assert q.drafted.tolist() == [2, 2, 2]
    assert np.allclose(q.tvd_sum, [0.5, 0.7, 0.9])
    assert q.rounds == 1
    assert q.depth_acceptance() == {1: 0.5, 2: 1.0, 3: 0.0}
    # entropy binning: 0.01 x3 -> bin 0; 0.7 -> bin 2; 3.0 -> bin 4; 5 -> inf
    assert q.ent_bin_drafted.tolist() == [3, 0, 1, 0, 1, 1]
    # round fraction = accepted/attempted = 2/4
    assert q.ewma_accept == pytest.approx(0.5)


def test_quality_stats_drafted_mask():
    q = QualityStats(depth=3)
    tvd = np.array([[0.1, 0.9, 0.9]])
    ent = np.zeros((1, 3))
    acc = np.array([[False, False, False]])
    drafted = np.array([[True, False, False]])      # tree: path stopped at d1
    q.update_round(tvd, ent, acc, drafted)
    assert q.drafted.tolist() == [1, 0, 0]
    assert q.attempted.tolist() == [1, 0, 0]
    assert np.allclose(q.tvd_sum, [0.1, 0.0, 0.0])  # undrafted TVD excluded
    assert q.ent_bin_drafted.sum() == 1


def test_quality_stats_merge_and_snapshot():
    a, b = QualityStats(depth=2), QualityStats(depth=2)
    tvd = np.full((1, 2), 0.5)
    ent = np.full((1, 2), 1.5)
    acc = np.array([[True, False]])
    a.update_round(tvd, ent, acc)
    b.update_round(tvd, ent, acc)
    a.merge(b)
    assert a.rounds == 2 and a.accepted.tolist() == [2, 0]
    snap = a.snapshot()
    json.dumps(snap)                                 # JSON-able end to end
    assert snap["rounds"] == 2
    with pytest.raises(ValueError):
        a.merge(QualityStats(depth=3))


def test_quality_stats_emit():
    from repro.obs import MetricsRegistry
    q = QualityStats(depth=2)
    q.update_round(np.zeros((1, 2)), np.zeros((1, 2)),
                   np.array([[True, True]]))
    reg = MetricsRegistry()
    q.emit(reg)
    assert "quality_accept_ewma" in reg
    assert "quality_rounds_total" in reg
    assert reg.to_prometheus().count("quality_") >= 4


# ------------------------------------------------- engine token identity

def _models(t_layers=2, d_layers=1):
    tcfg = ModelConfig(name="t", arch_type="dense", num_layers=t_layers,
                       **BASE)
    dcfg = ModelConfig(name="d", arch_type="dense", num_layers=d_layers,
                       **BASE)
    t, d = Model(tcfg), Model(dcfg)
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    return t, d, tp, dp


def _serve(t, d, tp, dp, quality, tree=None):
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(prompt=rng.integers(0, 64, 10).astype(np.int32),
                         max_new_tokens=6, request_id=i,
                         tenant="even" if i % 2 == 0 else "odd")
            for i in range(3)]
    eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp,
                           sd=SDConfig(gamma=3, temperature=0.0), tree=tree,
                           max_batch=2, max_seq_len=48, quality=quality)
    res = eng.serve(reqs)
    return eng, {r.request_id: r.tokens.tolist() for r in res}


def test_engine_chain_quality_token_identity():
    t, d, tp, dp = _models()
    _, off = _serve(t, d, tp, dp, quality=False)
    eng, on = _serve(t, d, tp, dp, quality=True)
    assert on == off, "quality telemetry must not perturb temp-0 tokens"
    q = eng.quality_stats
    assert q.rounds > 0 and q.attempted.sum() > 0
    # per-request and per-tenant pools saw every round the engine pooled
    assert all(eng.stats[i].quality.rounds > 0 for i in range(3))
    assert set(eng.tenant_quality) == {"even", "odd"}
    assert sum(ts.rounds for ts in eng.tenant_quality.values()) >= q.rounds


def test_engine_tree_quality_token_identity():
    t, d, tp, dp = _models()
    tree = TreeSpec((2, 2))
    _, off = _serve(t, d, tp, dp, quality=False, tree=tree)
    eng, on = _serve(t, d, tp, dp, quality=True, tree=tree)
    assert on == off
    q = eng.quality_stats
    assert q.rounds > 0 and q.depth == tree.depth
    # tree path repeats its stop node: depth d is drafted only when reached
    assert all(q.drafted[i] >= q.drafted[i + 1]
               for i in range(q.depth - 1))


# ------------------------------------------------------------------ SLO

def test_slo_tracker_multi_window_breach():
    cfg = SLOConfig(ttft_ms=10.0, tpot_ms=None, target=0.5,
                    fast_window=4, slow_window=8,
                    fast_burn=1.5, slow_burn=1.0)
    tr = SLOTracker(cfg)
    for _ in range(8):
        assert tr.observe(0.001, 0.0) == []        # all good: no breach
    fired = []
    for i in range(6):
        fired.extend(tr.observe(0.02, 0.0))        # sustained badness
    assert "ttft" in fired and tr.breached
    assert tr.bad_total["ttft"] == 6
    # a single blip after recovery does not re-fire (slow window gates)
    tr2 = SLOTracker(cfg)
    for _ in range(8):
        tr2.observe(0.001, 0.0)
    assert tr2.observe(0.02, 0.0) == []


def test_slo_tracker_summary_emit_snapshot():
    from repro.obs import MetricsRegistry
    tr = SLOTracker(SLOConfig(ttft_ms=5.0, tpot_ms=1.0))
    for i in range(50):
        tr.observe(0.001 * (i % 10), 0.0005)
    assert "ttft" in tr.summary() and "tpot" in tr.summary()
    reg = MetricsRegistry()
    tr.emit(reg)
    assert "slo_ttft_burn_fast" in reg and "slo_tpot_bad_total" in reg
    json.dumps(tr.snapshot())


# -------------------------------------------------------- flight recorder

def test_flight_recorder_ring_and_dump(tmp_path):
    rec = FlightRecorder(out_dir=str(tmp_path), capacity=4, max_dumps=2)
    for i in range(10):
        rec.record_round(slots={0: {"committed": i}},
                         tvd=np.float32(0.5), mask=np.array([True, False]))
    assert rec.rounds_seen == 10 and len(rec.ring) == 4
    path = rec.dump("drift_alarm", context={"ewma": float("nan")})
    bundle = json.load(open(path))
    assert bundle["reason"] == "drift_alarm"
    assert [r["round"] for r in bundle["rounds"]] == [7, 8, 9, 10]
    assert bundle["rounds"][-1]["mask"] == [True, False]
    assert bundle["context"]["ewma"] is None       # NaN -> null, valid JSON
    rec.dump("slo_breach")
    assert rec.dump("slo_breach") is None          # capped ...
    assert len(rec.triggers) == 3                  # ... but still counted


def test_engine_crash_dumps_flight_bundle(tmp_path):
    t, d, tp, dp = _models()
    eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp,
                           sd=SDConfig(gamma=2, temperature=0.0),
                           max_batch=2, max_seq_len=48, quality=True,
                           flight_record=True, flight_dir=str(tmp_path))
    eng.submit(ServeRequest(prompt=np.arange(8, dtype=np.int32),
                            max_new_tokens=6, request_id=0))
    stream = eng.stream()
    next(stream)                                   # engine is mid-run
    eng._slots[0].stats = None                     # induce a crash
    with pytest.raises(AttributeError):
        for _ in stream:
            pass
    crash = [p for p in os.listdir(tmp_path) if "crash" in p]
    assert len(crash) == 1
    bundle = json.load(open(tmp_path / crash[0]))
    assert "AttributeError" in bundle["context"]["error"]


# ------------------------------------------------------------- satellites

def test_latency_percentiles_nan_on_empty():
    out = latency_percentiles([])
    assert all(np.isnan(v) for v in out.values())
    out = latency_percentiles([0.1, 0.2])
    assert out["p50_ms"] > 0


def test_latency_percentiles_accepts_sketch():
    rng = np.random.default_rng(0)
    vals = rng.exponential(0.05, 3000)
    sk = GKSketch(eps=0.005)
    for v in vals:
        sk.insert(v)
    out = latency_percentiles(sk)
    ref = latency_percentiles(vals)
    for k in out:
        assert out[k] == pytest.approx(ref[k], rel=0.1)
    assert all(np.isnan(v) for v in latency_percentiles(GKSketch()).values())


def test_histogram_bucket_validation():
    Histogram("ok", buckets=(0.1, 0.5, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=())
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(0.5, 0.5, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(1.0, 0.5))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(0.1, float("inf")))


def test_log_buckets():
    edges = log_buckets(0.001, 10.0)
    assert all(b < a for b, a in zip(edges, edges[1:]))
    assert edges[0] == 0.001 and edges[-1] >= 10.0
    Histogram("h", buckets=edges)                  # passes strict validation
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 10.0, per_decade=0)


def test_accept_hist_emitted():
    from repro.core.metrics import SDStats
    from repro.obs import MetricsRegistry
    s = SDStats()
    for n in (1, 3, 3, 4):
        s.update(n)
    reg = MetricsRegistry()
    s.emit(reg)
    assert "sd_blocks_committed_3_total" in reg
    assert reg.counter("sd_blocks_committed_3_total").value == 2


def test_acceptance_report_math():
    q = QualityStats(depth=2)
    # 10 rounds of 1 row each: 6 accept depth1, of those 3 accept depth2
    for i in range(10):
        acc = np.array([[i < 6, i < 3]])
        q.update_round(np.zeros((1, 2)), np.zeros((1, 2)), acc)
    rep = acceptance_report(q, gamma=2)
    assert rep["alpha"] == pytest.approx(9 / 16)
    assert rep["tau_measured"] == pytest.approx(1 + 9 / 10)
    d1, d2 = rep["depths"]
    assert d1["conditional_acceptance"] == pytest.approx(0.6)
    assert d2["conditional_acceptance"] == pytest.approx(0.5)
    a = rep["alpha"]
    assert rep["tau_iid"] == pytest.approx((1 - a ** 3) / (1 - a))
