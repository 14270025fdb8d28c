"""Continuous-batching serving: paged KV pool, scheduler, engine equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.speculative import SDConfig
from repro.models import Model
from repro.serving import (ContinuousEngine, PagedKVPool, Request,
                           ServeRequest, ServingEngine, apply_page_permutation)

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
            attn_chunk=16, remat=False)


@pytest.fixture(scope="module")
def models():
    tcfg = ModelConfig(name="t", arch_type="dense", num_layers=4, **BASE)
    dcfg = ModelConfig(name="d", arch_type="dense", num_layers=2, **BASE)
    t, d = Model(tcfg), Model(dcfg)
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    return t, d, tp, dp


# ------------------------------------------------------------------ kv pool

def test_pool_alloc_free_roundtrip():
    pool = PagedKVPool(num_pages=9, page_size=4, max_pages_per_seq=4)
    a = pool.alloc(0, 10)            # 3 pages
    b = pool.alloc(1, 8)             # 2 pages
    assert len(a) == 3 and len(b) == 2
    assert 0 not in a + b            # null page never handed out
    assert pool.num_free == 3
    row = pool.table_row(0)
    assert row.shape == (4,) and list(row[:3]) == a and row[3] == 0
    pool.free_slot(0)
    assert pool.num_free == 6
    assert list(pool.table_row(0)) == [0, 0, 0, 0]


def test_pool_admission_bounds():
    pool = PagedKVPool(num_pages=5, page_size=4, max_pages_per_seq=3)
    assert pool.can_alloc(12)        # 3 pages of 4 free
    assert not pool.can_alloc(16)    # 4 pages > max_pages_per_seq
    pool.alloc(0, 12)
    assert not pool.can_alloc(8)     # only 1 page left
    with pytest.raises(MemoryError):
        pool.alloc(1, 8)


def test_pool_compact_renumbers_and_permutes():
    pool = PagedKVPool(num_pages=8, page_size=2, max_pages_per_seq=4)
    assert pool.alloc(0, 4) == [1, 2]    # fresh pool allocates ascending
    assert pool.alloc(1, 4) == [3, 4]
    pool.free_slot(0)
    assert pool.table_row(1)[:2].tolist() == [3, 4]
    perm = pool.compact()
    assert perm is not None
    assert sorted(perm.tolist()) == list(range(8))
    assert pool.table_row(1)[:2].tolist() == [1, 2]
    # device-side gather follows the same renumbering
    pages = jnp.arange(8)[:, None] * jnp.ones((1, 2))
    moved = apply_page_permutation({"rem": ({"page_pos": pages},)},
                                   perm)["rem"][0]["page_pos"]
    assert moved[1, 0] == perm[1]


def test_scheduler_future_arrival_never_blocks_arrived_work():
    from repro.serving import Scheduler
    sched = Scheduler(policy="priority")
    urgent_later = ServeRequest(prompt=np.zeros(4, np.int32), request_id=0,
                                priority=0, arrival_time_s=5.0)
    waiting_now = ServeRequest(prompt=np.zeros(4, np.int32), request_id=1,
                               priority=9, arrival_time_s=0.0)
    sched.submit(urgent_later)
    sched.submit(waiting_now)
    got = sched.pop_admissible(now_s=1.0, can_admit=lambda r: True)
    assert got is waiting_now            # future high-priority head skipped
    # a capacity-blocked arrived head does hold the line
    sched.submit(waiting_now)
    assert sched.pop_admissible(1.0, lambda r: False) is None
    assert len(sched) == 2
    # once time passes, priority order applies among arrived requests
    assert sched.pop_admissible(6.0, lambda r: True) is urgent_later


# ---------------------------------------------------------------- engines

def _requests(rng, lens, max_new):
    return [Request(prompt=rng.integers(0, 64, L).astype(np.int32),
                    max_new_tokens=m, request_id=i)
            for i, (L, m) in enumerate(zip(lens, max_new))]


def test_continuous_matches_static_greedy(models):
    """Acceptance: temperature-0 token-identical to the static engine."""
    t, d, tp, dp = models
    rng = np.random.default_rng(0)
    reqs = _requests(rng, [8, 8, 8], [12, 12, 12])
    sdc = SDConfig(gamma=3, temperature=0.0)
    static = ServingEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp, sd=sdc, batch_size=4).serve(reqs)
    cont = ContinuousEngine(target=t, target_params=tp, draft=d,
                            draft_params=dp, sd=sdc, max_batch=4,
                            max_seq_len=32, page_size=8,
                            prefill_chunk=8).serve(reqs)
    static = sorted(static, key=lambda r: r.request_id)
    for a, b in zip(static, cont):
        assert a.request_id == b.request_id
        assert np.array_equal(a.tokens, b.tokens), a.request_id


def test_continuous_mixed_lengths_greedy(models):
    """Mixed (prompt_len, max_new) — static degenerates to per-request
    batches; continuous must still match token-for-token."""
    t, d, tp, dp = models
    rng = np.random.default_rng(1)
    reqs = _requests(rng, [6, 11, 16, 9], [10, 7, 13, 5])
    sdc = SDConfig(gamma=3, temperature=0.0)
    static = ServingEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp, sd=sdc).serve(reqs)
    cont = ContinuousEngine(target=t, target_params=tp, draft=d,
                            draft_params=dp, sd=sdc, max_batch=3,
                            max_seq_len=32, page_size=4,
                            prefill_chunk=8).serve(reqs)
    static = sorted(static, key=lambda r: r.request_id)
    for a, b in zip(static, cont):
        assert np.array_equal(a.tokens, b.tokens), a.request_id


def test_staggered_arrivals_join_running_batch(models):
    """With fewer slots than requests and staggered arrivals, later requests
    must be admitted as earlier ones retire, and all must complete."""
    t, d, tp, dp = models
    rng = np.random.default_rng(2)
    sdc = SDConfig(gamma=2, temperature=0.0)
    eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp, sd=sdc, max_batch=2,
                           max_seq_len=32, page_size=4, prefill_chunk=8)
    lens, max_new = [6, 12, 8, 10], [8, 6, 10, 7]
    streamed = {}
    for i, (L, m) in enumerate(zip(lens, max_new)):
        eng.submit(ServeRequest(
            prompt=rng.integers(0, 64, L).astype(np.int32),
            max_new_tokens=m, request_id=i, arrival_time_s=0.0,
            on_token=lambda rid, toks: streamed.setdefault(rid, []).extend(
                toks.tolist())))
    results = {r.request_id: r for r in eng.run()}
    assert sorted(results) == [0, 1, 2, 3]
    for i, m in enumerate(max_new):
        assert results[i].tokens.shape == (m,)
        # streamed tokens == final tokens, in order
        assert streamed[i] == results[i].tokens.tolist()
    tel = eng.telemetry
    assert tel.admitted == 4 and tel.completed == 4
    # only 2 slots: someone had to wait in queue while the batch was full
    assert tel.max_queue_depth >= 1
    assert max(tel.active_rows) <= 2
    # retire-then-admit actually happened across the run
    stats = [eng.stats[i] for i in range(4)]
    assert any(s.queue_wait_s > 0 for s in stats)
    for s in stats:
        assert s.new_tokens == max_new[s.request_id]
        assert s.finish_time_s >= s.first_token_time_s >= s.submit_time_s
        assert s.sd.tau >= 1.0


def test_priority_policy_orders_admission(models):
    t, d, tp, dp = models
    rng = np.random.default_rng(3)
    sdc = SDConfig(gamma=2, temperature=0.0)
    eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp, sd=sdc, max_batch=1,
                           max_seq_len=24, page_size=4, prefill_chunk=8,
                           policy="priority")
    order = []
    for i, pri in enumerate([5, 1, 3]):
        eng.submit(ServeRequest(prompt=rng.integers(0, 64, 6).astype(np.int32),
                                max_new_tokens=4, request_id=i, priority=pri,
                                on_finish=lambda r: order.append(r.request_id)))
    eng.run()
    assert order == [1, 2, 0]      # lowest priority value first


def _clear_compiled():
    """Drop every compiled round and step so the next engine retraces
    (the attention read path is chosen at trace time)."""
    from repro.core import speculative
    for f in vars(speculative).values():
        if hasattr(f, "cache_clear"):
            f.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["private_pages", "prefix_cache"])
def test_paged_kernel_engine_matches_gather(monkeypatch, prefix_cache):
    """At temperature 0 the engine emits the same tokens with the paged-
    attention kernel forced on (interpret mode) as on the gather path, with
    and without rows sharing prefix pages; the page counters read each
    round's live pages against the tables' span."""
    from repro.models import attention as A
    f32 = dict(BASE, dtype="float32")
    t = Model(ModelConfig(name="t32", arch_type="dense", num_layers=2, **f32))
    d = Model(ModelConfig(name="d32", arch_type="dense", num_layers=1, **f32))
    tp, _ = t.init(jax.random.PRNGKey(0))
    dp, _ = d.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 64, 8)
    reqs = [ServeRequest(prompt=np.concatenate(
                [shared, rng.integers(0, 64, n)]).astype(np.int32),
                max_new_tokens=m, request_id=i)
            for i, (n, m) in enumerate([(3, 9), (7, 6), (1, 11)])]

    def serve(kernel):
        monkeypatch.setattr(A, "PAGED_FASTPATH", kernel)
        _clear_compiled()
        eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                               draft_params=dp,
                               sd=SDConfig(gamma=3, temperature=0.0),
                               max_batch=2, max_seq_len=32, page_size=4,
                               prefill_chunk=8, prefix_cache=prefix_cache)
        live, rows, run_round = [0], [0], eng._decode_round

        def counted_round():
            for i, slot in enumerate(eng._slots):
                if slot.state == "decode":
                    last = int(eng._lengths_h[i]) + eng._span - 1
                    live[0] += int(np.count_nonzero(
                        eng._table_h[i, :last // eng.page_size + 1]))
                    rows[0] += 1
            return run_round()

        eng._decode_round = counted_round
        for r in reqs:
            eng.submit(r)
        out = {r.request_id: r.tokens for r in eng.run()}
        tel = eng.telemetry
        assert tel.kv_pages_read == live[0] > 0
        assert tel.kv_pages_spanned == rows[0] * eng._table_h.shape[1]
        assert tel.kv_pages_read <= tel.kv_pages_spanned
        if prefix_cache:
            assert eng.prefix.tel.hits >= 1
        return out

    try:
        gathered, kernel = serve(False), serve(True)
    finally:
        _clear_compiled()
    assert sorted(kernel) == sorted(gathered) == [0, 1, 2]
    for rid in gathered:
        assert np.array_equal(kernel[rid], gathered[rid]), rid


def test_engine_rejects_oversized_and_recurrent(models):
    t, d, tp, dp = models
    eng = ContinuousEngine(target=t, target_params=tp, draft=d,
                           draft_params=dp, sd=SDConfig(temperature=0.0),
                           max_seq_len=16)
    with pytest.raises(ValueError):
        eng.submit(ServeRequest(prompt=np.zeros(10, np.int32),
                                max_new_tokens=10))
    # fits max_seq_len but can never fit a deliberately tiny pool: must be
    # rejected at submit instead of hanging run() forever
    tiny = ContinuousEngine(target=t, target_params=tp, draft=d,
                            draft_params=dp, sd=SDConfig(temperature=0.0),
                            max_seq_len=64, num_pages=4, page_size=8)
    with pytest.raises(ValueError, match="KV pages"):
        tiny.submit(ServeRequest(prompt=np.zeros(20, np.int32),
                                 max_new_tokens=20))
    from repro.configs.base import MAMBA, ATTN
    hcfg = ModelConfig(name="h", arch_type="dense", num_layers=2,
                       layer_pattern=(MAMBA, ATTN), ssm_state_dim=16,
                       ssm_head_dim=16, ssm_chunk=8, **BASE)
    with pytest.raises(ValueError):
        ContinuousEngine(target=Model(hcfg), target_params=None, draft=d,
                         draft_params=dp)
