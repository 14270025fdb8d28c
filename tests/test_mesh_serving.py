"""Serving across chips, on four virtual CPU devices in one subprocess (the
device count must not leak into this test process): the paged kernel called
through ``shard_map`` over the model axis, and a Yi-shaped model (Hkv 4,
G 8) served tensor parallel by ``ContinuousEngine`` under
``use_mesh(mesh, (), "model")`` with the kernel path forced, so that each
chip holds one KV head of each pool.

The served-path logits are held to a float32 full forward at ``highest``
precision within ``LOGIT_TOL``: the served model is float32 too, so the two
differ only in summation order (about 1e-6), while the same reference in
bfloat16 (8 mantissa bits, relative error about 4e-3 per product) misses by
more than ten times the limit; the script computes that reference as well,
to show the limit would catch it."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

LOGIT_TOL = 1e-4

SCRIPT = textwrap.dedent("""
    import json, warnings
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.analysis.recompile import (CompileWatcher, count_device_gets,
                                          _engine_signatures)
    from repro.configs.base import ModelConfig
    from repro.core.speculative import SDConfig, trim_paged_cache
    from repro.kernels import ops, ref
    from repro.models import attention
    from repro.models.model import Model
    from repro.serving import ContinuousEngine, ServeRequest
    from repro.serving.kv_pool import pool_shardings
    from repro.sharding.context import use_mesh
    from repro.sharding.rules import INFERENCE_RULES, make_param_shardings

    out = {}
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))

    # ---- the kernel through shard_map: bf16, one KV head a chip, G 8, T 4
    L, NP, page, hkv, hd, G, T = 2, 20, 8, 4, 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    k = jax.random.normal(ks[0], (L, NP, page, hkv, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (L, NP, page, hkv, hd)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[2], (3, T, hkv * G, hd)).astype(jnp.bfloat16)
    lasts = [11, 30, 5]
    table = np.zeros((3, 6), np.int32)
    pp = np.full((L, NP, page), -1, np.int32)
    nxt = 1
    for b, last in enumerate(lasts):
        if b == 2:
            continue                                  # an inactive row
        for j in range(last // page + 1):
            table[b, j], nxt = nxt, nxt + 1
            for s in range(page):
                if j * page + s <= last:
                    pp[:, table[b, j], s] = j * page + s
    pos = np.array(lasts)[:, None] - T + 1 + np.arange(T)[None]
    args = (q, k, v, jnp.asarray(pp), jnp.asarray(table),
            jnp.asarray(pos, jnp.int32))
    alone = ops.paged_attention(*args, layer=1)
    want = ref.ref_paged_attention(*args, layer=1)
    seen = []
    real = ops.paged_attention

    def spy(q, k_pool, *a, **kw):
        seen.append(k_pool.shape[-2])
        return real(q, k_pool, *a, **kw)
    ops.paged_attention = spy
    heads, pools = (NamedSharding(mesh, P(None, None, "model")),
                    NamedSharding(mesh, P(None, None, None, "model")))
    rep = NamedSharding(mesh, P())
    placed = [jax.device_put(x, s) for x, s in
              zip(args, (heads, pools, pools, rep, rep, rep))]
    with use_mesh(mesh, (), "model"):
        split = jax.jit(lambda *a: attention._paged_kernel(
            *a, 1, None, None))(*placed)
    ops.paged_attention = real
    out["kernel"] = {
        "vs_alone": float(jnp.max(jnp.abs(split - alone))),
        "vs_ref": float(jnp.max(jnp.abs(split - want))),
        "local_heads": sorted(set(seen)),
        "out_split": str(split.sharding.spec)}

    # ---- a Yi-shaped model, float32, served tensor parallel

    def cfg(name, layers, d, h, dtype="float32"):
        return ModelConfig(name=name, arch_type="dense", num_layers=layers,
                           d_model=d, num_heads=h, num_kv_heads=4,
                           head_dim=16, d_ff=128, vocab_size=128,
                           attn_chunk=8, remat=False, dtype=dtype,
                           param_dtype=dtype)
    t, d = Model(cfg("t", 2, 64, 32)), Model(cfg("d", 1, 32, 8))

    def init(model, seed):
        specs = []

        def params(key):
            p, s = model.init(key)
            specs.append(s)
            return p
        return jax.jit(params)(jax.random.PRNGKey(seed)), specs[0]
    (tp, ts), (dp, ds) = init(t, 1), init(d, 2)

    def engine(params):
        return ContinuousEngine(
            target=t, target_params=params[0], draft=d,
            draft_params=params[1], sd=SDConfig(gamma=3, temperature=0.0),
            max_batch=4, max_seq_len=48, page_size=8, num_pages=32,
            prefill_chunk=8)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 128, n).astype(np.int32), m)
            for n, m in [(13, 14), (19, 7)]]

    def serve(eng):
        for i, (p, m) in enumerate(reqs):
            eng.submit(ServeRequest(prompt=p, max_new_tokens=m,
                                    request_id=i))
        done = {}
        while eng.has_work():
            for ev in eng.step():
                if ev[0] == "finish":
                    done[ev[1]] = ev[2].tokens.tolist()
        return done

    attention.PAGED_FASTPATH = False          # one device: the gather
    one = serve(engine((tp, dp)))
    attention.PAGED_FASTPATH = True           # across chips: the kernel
    with use_mesh(mesh, (), "model"):
        sp = tuple(jax.device_put(p, make_param_shardings(
            s, p, mesh, INFERENCE_RULES)) for p, s in ((tp, ts), (dp, ds)))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            seen.clear()
            ops.paged_attention = spy
            e1 = engine(sp)
            got = serve(e1)
            ops.paged_attention = real
            with CompileWatcher() as warm:
                got2 = serve(engine(sp))
            e3 = engine(sp)
            e3.submit(ServeRequest(prompt=reqs[0][0], max_new_tokens=30,
                                   request_id=0))
            while e3.telemetry.decode_rounds < 2:
                e3.step()
            try:
                with jax.transfer_guard("disallow"), \\
                        count_device_gets() as gets:
                    e3._decode_round()
                implicit = None
            except Exception as e:                    # noqa: BLE001
                implicit = str(e)[:300]
        split = {}
        for name in ("t_cache", "d_cache"):
            for path, x in jax.tree_util.tree_flatten_with_path(
                    e1._state[name])[0]:
                local = x.addressable_shards[0].data.shape
                split[f"{name}{jax.tree_util.keystr(path)}"] = [
                    list(x.shape), list(local), len(x.sharding.device_set)]
        e1._pin()               # the layout each donated dispatch meets
        rest = {n: str(x.sharding.spec) for n, x in e1._state.items()
                if n not in ("t_cache", "d_cache")}

        # prefill in chunks, then a 4-token step, through the paged pool
        seq = jnp.asarray(rng.integers(0, 128, 17), jnp.int32)
        pool = jax.jit(lambda: t.init_paged_cache(8, 8),
                       out_shardings=pool_shardings(
                           jax.eval_shape(lambda: t.init_paged_cache(8, 8)),
                           mesh))()
        step = e1._t_step                     # the engine's prefill step
        table = jnp.asarray([[3, 1, 6, 0]], jnp.int32)
        served = []
        for start in (0, 8):
            toks = jnp.zeros((1, 8), jnp.int32).at[0, :min(8, 13 - start)].set(
                seq[start:min(start + 8, 13)])
            lg, pool = step(sp[0], toks, jnp.arange(start, start + 8)[None],
                            pool, page_table=table)
            served.append(lg[0, :min(8, 13 - start)])
        pool = trim_paged_cache(pool, table, jnp.asarray([12]))
        lg, pool = step(sp[0], seq[None, 13:17], jnp.arange(13, 17)[None],
                        pool, page_table=table)
        served.append(lg[0])
    served = np.concatenate([np.asarray(x) for x in served])
    with jax.default_matmul_precision("highest"):
        full = np.asarray(t.logits(tp, seq[None])[0][0])
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), tp)
        t16 = Model(cfg("t", 2, 64, 32, "bfloat16"))
        bf16 = np.asarray(t16.logits(low, seq[None])[0][0], np.float32)
    out["served"] = {
        "same_tokens": got == one, "same_tokens_warm": got2 == one,
        "n_tokens": sum(len(x) for x in one.values()),
        "local_heads": sorted(set(seen)), "split": split, "rest": rest,
        "warnings": [str(w.message)[:200] for w in caught],
        "warm_compiles": warm.n_compiles,
        "warm_signatures": _engine_signatures(warm.signatures),
        "device_gets": gets[0] if implicit is None else None,
        "implicit": implicit,
        "logit_gap": float(np.max(np.abs(served - full))),
        "bf16_gap": float(np.max(np.abs(bf16 - full)))}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def across_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                     env.get("PYTHONPATH")) if p])
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_kernel_under_shard_map_equals_the_kernel_alone(across_chips):
    k = across_chips["kernel"]
    assert k["local_heads"] == [1]            # one bf16 KV head a chip
    assert k["out_split"] == "PartitionSpec(None, None, 'model')"
    assert k["vs_alone"] <= 1e-6
    assert k["vs_ref"] <= 2e-2                # bf16 pools, as in test_kernels


def test_served_tokens_equal_one_device(across_chips):
    s = across_chips["served"]
    assert s["local_heads"] == [1]            # the kernel ran, per chip
    assert s["n_tokens"] == 14 + 7
    assert s["same_tokens"] and s["same_tokens_warm"]


def test_served_logits_match_a_float32_reference(across_chips):
    s = across_chips["served"]
    assert s["logit_gap"] <= LOGIT_TOL
    assert s["bf16_gap"] > 10 * LOGIT_TOL     # the limit catches bf16


def test_each_chip_holds_a_quarter_of_each_pool(across_chips):
    s = across_chips["served"]
    for name, (shape, local, devices) in s["split"].items():
        assert devices == 4, name
        if name.endswith("['k']") or name.endswith("['v']"):
            assert local[-2] * 4 == shape[-2] and local[:-2] == shape[:-2]
        else:                                 # slot positions: replicated
            assert local == shape, name
    assert set(s["rest"].values()) == {"PartitionSpec()"}


def test_no_donation_warning_and_rc002_to_rc004_hold(across_chips):
    s = across_chips["served"]
    assert not [w for w in s["warnings"] if "donat" in w], s["warnings"]
    assert s["warm_compiles"] == 0 and s["warm_signatures"] == []   # RC002
    assert s["implicit"] is None                                     # RC004
    assert s["device_gets"] == 1                                     # RC003
