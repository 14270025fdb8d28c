"""Window accounting (ramp exclusion, drain cap, failed count), the traffic
generator's fixed work per seed, and the percentiles."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from lib import stats, traffic  # noqa: E402
from lib.driver import EngineTracer, run_window  # noqa: E402


class FakeEngine:
    """Streams one token per request per step after a first-token step;
    requests in ``stall`` never finish."""

    def __init__(self, tracer, stall=()):
        self.tracer, self.stall = tracer, set(stall)
        self._key = 0
        self.queue, self.active = [], {}

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        time.sleep(0.002)
        events = []
        with self.tracer.span("decode_round"):
            for rid, (req, n) in list(self.active.items()):
                if rid in self.stall:
                    continue
                events.append(("token", rid, np.asarray([7])))
                n += 1
                self.active[rid] = (req, n)
                if n == req.max_new_tokens:
                    del self.active[rid]
                    events.append(("finish", rid, _Result(n)))
        while self.queue:
            req = self.queue.pop(0)
            self.tracer.async_instant("admit", req.request_id, slot=0)
            self.active[req.request_id] = (req, 1)
            events.append(("token", req.request_id, np.asarray([7])))
        return events


class _Result:
    def __init__(self, n):
        self.tokens = np.zeros((n,), np.int64)


def _mix(loop, **kw):
    mix = {"loop": loop, "ramp_s": 0.2, "drain_cap_s": 0.5,
           "arrivals": {"process": "poisson"}, "rate_per_s": 40.0,
           "clients": 2, "pool": 200, "backlog": 50,
           "prompt_len": {"dist": "uniform", "min": 4, "max": 8,
                          "levels": 4},
           "max_new": {"dist": "uniform", "min": 3, "max": 9, "levels": 4}}
    mix.update(kw)
    return mix


def _run(mix, seconds=0.5, stall=()):
    reqs = traffic.generate(mix, 1234, seconds, 100)
    tr = EngineTracer()
    return reqs, run_window(FakeEngine(tr, stall), reqs, mix, seconds, tr)


def test_open_loop_counts_window_arrivals_only():
    mix = _mix("open")
    reqs, rec = _run(mix)
    ramp = [r.rid for r in reqs if r.arrival_s < 0.2]
    assert len(ramp) == round(40.0 * 0.2)
    assert not any(r.counted for r in reqs if r.rid in ramp)
    assert sorted(rec.counted) == [r.rid for r in reqs if r.counted]
    assert len(rec.counted) == round(40.0 * 0.5)
    assert not set(ramp) & set(rec.counted)
    assert all(rid in rec.finish for rid in rec.counted)
    # tokens counted only for steps that ended inside the window
    assert 0 < rec.window_tokens < sum(r.max_new for r in reqs)


def test_drain_cap_leaves_stalled_requests_failed():
    mix = _mix("open")
    reqs = traffic.generate(mix, 1234, 0.5, 100)
    stalled = [r.rid for r in reqs if r.counted][:2]
    _, rec = _run(mix, stall=stalled)
    failed = [rid for rid in rec.counted if rid not in rec.finish]
    assert sorted(failed) == sorted(stalled)
    assert rec.drain_end >= rec.win_end + mix["drain_cap_s"]
    assert rec.drain_end < rec.win_end + mix["drain_cap_s"] + 0.2


def test_closed_loop_keeps_clients_busy_and_counts_window_sends():
    mix = _mix("closed")
    _, rec = _run(mix)
    sent = sorted(rec.sent.values())
    assert sent[:2] == [0.0, 0.0]
    assert set(rec.counted) == {rid for rid, t in rec.sent.items()
                                if rec.win_start <= t < rec.win_end}
    assert len(rec.counted) > 2


def test_backlog_counts_first_tokens_in_window():
    mix = _mix("backlog")
    _, rec = _run(mix)
    assert len(rec.sent) == 50
    assert set(rec.counted) == {rid for rid, t in rec.first.items()
                                if rec.win_start <= t < rec.win_end}


@pytest.mark.parametrize("loop", ["open", "closed", "backlog"])
def test_every_seed_gets_the_same_work_in_another_order(loop):
    mix = _mix(loop)
    a = traffic.generate(mix, 1, 3.0, 1000)
    b = traffic.generate(mix, 2 ** 40 + 3, 3.0, 1000)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(f, a)) == sorted(map(f, b))
        assert list(map(f, a)) != list(map(f, b))
    if loop == "open":
        ta = [r.arrival_s for r in a if r.counted]
        assert len(ta) == round(40.0 * 3.0)
        assert all(0.2 < t < 3.2 for t in ta)
        assert all(0.0 < r.arrival_s < 0.2 or 3.2 < r.arrival_s < 3.7
                   for r in a if not r.counted)
        gaps = lambda rs: sorted(np.round(np.diff([r.arrival_s for r in rs
                                                   if r.counted]), 9))
        assert len(gaps(a)) == len(gaps(b))


def test_length_levels_are_strata_medians_of_the_distribution():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32,
         "max": 3072, "levels": 8}
    v = traffic.length_levels(d)
    assert len(v) == 10 and list(v) == sorted(v)
    assert (v[0], v[-1]) == (32, 3072)            # the clip ends
    assert v[4] < 512 < v[5]                      # median between strata 4, 5
    assert np.isclose(np.sqrt(v[4] * v[5]), 512, rtol=0.01)
    u = traffic.length_levels({"dist": "uniform", "min": 16, "max": 64,
                               "levels": 8})
    assert list(u) == [16, 19, 25, 31, 37, 43, 49, 55, 61, 64]
    assert len(traffic.shapes({"prompt_len": d, "max_new": d})) == 100


def test_a_segment_serves_the_quantiles_and_the_clipped_tail():
    """41 requests (phi4-chat's window) take the 41 quantiles of each
    distribution at their nearest levels: the clip ends come in, and the
    mean is the clipped distribution's within a few per cent."""
    p = {"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32,
         "max": 3072, "levels": 8}
    m = {"dist": "lognormal", "median": 160, "sigma": 0.7, "min": 16,
         "max": 768, "levels": 8}
    z = np.random.default_rng(0).standard_normal(200_000)
    for d in (p, m):
        s = traffic.segment_lengths(d, 41)
        assert len(s) == 41 and set(s) <= set(traffic.length_levels(d))
        assert s.max() == d["max"]
        true = np.clip(d["median"] * np.exp(d["sigma"] * z), d["min"],
                       d["max"]).mean()
        assert abs(s.mean() / true - 1) < 0.05


def test_open_loop_keeps_sending_through_the_drain():
    """Arrivals go on after the window, uncounted, until the counted
    requests have finished."""
    mix = _mix("open", drain_cap_s=2.0,
               max_new={"dist": "uniform", "min": 20, "max": 40,
                        "levels": 2})
    reqs, rec = _run(mix, seconds=0.3)
    late = [rid for rid, t in rec.sent.items() if t >= rec.win_end]
    assert late and not set(late) & set(rec.counted)
    assert all(rid in rec.finish for rid in rec.counted)
    assert len(rec.sent) < len(reqs)              # stopped once they had


def test_percentile_is_linear_between_order_statistics():
    v = list(range(1, 11))
    assert stats.percentile(v, 90) == pytest.approx(9.1)
    assert stats.percentile(v, 50) == pytest.approx(5.5)
    assert stats.percentile(v, 90) == pytest.approx(np.percentile(v, 90))
    assert np.isnan(stats.percentile([], 90))
