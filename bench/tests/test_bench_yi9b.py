"""The yi9b-chat cell: Yi-9B whole on a v5e-4, tensor parallel. Its files
load by name, its counts match the published widths, and the two metrics
it adds (``round_collective_ms``, ``paged_attn_hbm_share``) read a small
recorded trace as computed by hand, and read a tiny cell served across four
virtual CPU devices (in a subprocess, so that the device count does not
leak into this test process)."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest
from jax.sharding import AbstractMesh

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from lib import collectives, counts, scopes, spec  # noqa: E402

SEED = 2 ** 33 + 41
MS = 10 ** 9          # picoseconds per millisecond
KV_TARGET, KV_DRAFTER = 98_304, 8_192    # bytes per token, every chip


def _metric_module(name):
    import importlib.util
    s = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the files

def test_config_and_mix_load_by_name():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "yi9b-chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yi-9b", "chat.yi9b", 4)
    config = spec.load_config(cell["config"])
    data = spec.load_config("yi-9b", BENCH / "tests" / "data")
    # the test data's model, with both assumed keys listed as changed
    mark = ("reduced", "assumed")
    assert ({k: v for k, v in config.items() if k not in mark}
            == {k: v for k, v in data.items() if k not in mark})
    entry = next(c for c in bench["configs"] if c["name"] == "yi-9b")
    assert entry["reduced"] == config["reduced"] == ["rope_theta",
                                                     "rms_norm_eps"]
    assert set(config["assumed"]) == set(config["reduced"])
    mix = spec.load_traffic(cell["traffic"])
    phi4 = spec.load_traffic("chat.phi4")
    for k in ("loop", "arrivals", "ramp_s", "drain_cap_s", "prompt_len",
              "max_new", "check"):
        assert mix[k] == phi4[k], k
    assert mix["serving"] == dict(phi4["serving"], num_pages=4096)
    assert f"({mix['rate_per_s']} req/s)" in cell["why"]


def _served(pages=8, page=16):
    out = []
    for model, _, _ in run.program_models(spec.load_config("yi-9b")):
        params = jax.eval_shape(lambda k, m=model: m.init(k)[0],
                                jax.random.PRNGKey(0))
        pool = jax.eval_shape(lambda m=model: m.init_paged_cache(pages, page))
        out.append((params, pool))
    return out


def test_counts_at_published_widths_and_a_chip_share_of_kv():
    from repro.serving.kv_pool import pool_shardings
    (tp, tpool), (dp, dpool) = _served()
    assert round(counts.param_count(tp) / 1e9, 3) == 8.829
    assert round(counts.param_count(dp) / 1e9, 3) == 0.178
    t, d = counts.model_counts(tp, tpool), counts.model_counts(dp, dpool)
    assert (t.kv_bytes_per_token, d.kv_bytes_per_token) == (KV_TARGET,
                                                            KV_DRAFTER)
    # across the cell's four chips each holds one KV head of each pool
    mesh = AbstractMesh((1, 4), ("data", "model"))
    per_chip = 0
    for pool in (tpool, dpool):
        shard = jax.tree.map(lambda s, x: s.shard_shape(x.shape),
                             pool_shardings(pool, mesh), pool)
        for name in ("k", "v"):
            shape = shard["groups"][0][name]
            assert shape[-2] == 1
            per_chip += shape[0] * shape[-1] * 2          # bf16
    assert per_chip == (KV_TARGET + KV_DRAFTER) // 4 == 26_624


def test_kernel_kv_bytes_of_a_round_by_hand():
    kv_bytes = _metric_module("paged_attn_hbm_share").kv_bytes
    t = SimpleNamespace(kv_bytes_per_token=KV_TARGET)
    d = SimpleNamespace(kv_bytes_per_token=KV_DRAFTER)
    # rows at 512 and 1024 committed tokens: the target's one verify pass
    # and the drafter's gamma + 1 = 4 passes each read them once
    assert kv_bytes(t, d, [512, 1024], 3) == 1536 * (98_304 + 4 * 8_192)
    assert kv_bytes(t, d, [], 3) == 0


# -------------------------------------------------- a recorded trace

# the window is [0, 10) ms; rounds ("jit_kept(13)", each followed by the
# window gather) at [1, 5) and [8, 9); a prefill step at [6, 7) outside them
MODULES = [("jit_kept(13)", 1, 5), ("jit__window_gather(5)", 5, 5.1),
           ("jit_kept(12)", 6, 7),
           ("jit_kept(13)", 8, 9), ("jit__window_gather(5)", 9, 9.1)]
R = "jit(kept)/"
KERNEL = "attention/sdpa/paged_kernel/shard_map/paged_attention"
# (name, start, end, tf_op, long_name)
OPS = [
    ("while.1", 1, 4.5, R + "verify/while", ""),
    ("all-reduce.1", 1.0, 1.2, R + "verify/while/body/attention/dot_general",
     ""),
    ("fusion.3", 1.2, 2.0, R + "verify/while/body/mlp/dot_general",
     "%fusion.3 = bf16[32,4,4096]{2,1,0} fusion(bf16[32,4,4096]{2,1,0} "
     "%all-reduce.1), kind=kLoop"),
    ("x.7", 2.0, 2.1, R + "verify/while/body/mlp/dot_general",
     "%x.7 = (bf16[32,4,4096]{2,1,0}, bf16[32,4,4096]{2,1,0}) "
     "all-gather-start(bf16[32,4,1024]{2,1,0} %fusion.3)"),
    ("all-gather-done.7", 2.1, 2.15, R + "verify/while/body/mlp", ""),
    ("paged_attention.5", 2.2, 3.2, R + "verify/while/body/" + KERNEL, ""),
    ("fusion.9", 4.5, 5.0, R + "commit/add", ""),
    ("all-reduce.2", 6.2, 6.4, R + "attention/dot_general", ""),
    ("paged_attention.6", 6.4, 6.9, R + KERNEL, ""),
    ("all-reduce.3", 8.0, 8.3, R + "draft/while/body/mlp/dot_general", ""),
    ("paged_attention.7", 8.3, 8.8, R + "draft/while/body/" + KERNEL, ""),
]
HOST = [("bench_window", 0, 10)]


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for ev in events:
            mid, _ = meta.setdefault(ev[0], (len(meta) + 1, ev[3:]))
            body.append(f"  events {{ metadata_id: {mid} offset_ps: "
                        f"{int(ev[1] * MS)} duration_ps: "
                        f"{int((ev[2] - ev[1]) * MS)} }}")
        body.append("}")
    for n, (m, stats) in meta.items():
        st = "".join(f' stats {{ metadata_id: {i} str_value: "{v}" }}'
                     for i, v in zip((1, 2), stats) if v)
        body.append(f'event_metadata {{ key: {m} value {{ id: {m} '
                    f'name: "{n}"{st} }} }}')
    body.append('stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
    body.append('stat_metadata { key: 2 value { id: 2 name: "long_name" } }')
    return [f'planes {{ id: {pid} name: "{name}"'] + body + ["}"]


def _write(tmp_path, ops=OPS, device=True):
    from jax.profiler import ProfileData
    planes = _plane(1, "/host:CPU", [("python", HOST)])
    if device:
        planes += _plane(2, "/device:TPU:0", [("XLA Modules", MODULES),
                                              ("XLA Ops", ops)])
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
    return tmp_path


@pytest.mark.parametrize("name,long_name,yes", [
    ("all-reduce.12", "", True),
    ("all-gather-start.3", "", True),
    ("%reduce-scatter.1", "", True),
    ("collective-permute-done.2", "", True),
    ("all-to-all.4", "", True),
    ("x.1", "%x.1 = (bf16[8]{0}, u32[]) all-reduce-start(bf16[8]{0} %p)",
     True),
    ("fusion.5", "%fusion.5 = bf16[8]{0:T(256)} fusion(bf16[8]{0} "
     "%all-reduce.3), kind=kLoop", False),
    ("reduce.2", "%reduce.2 = f32[] reduce(f32[8]{0} %a, f32[] %c)", False),
    ("paged_attention.5", "", False),
])
def test_collective_instructions(name, long_name, yes):
    assert collectives.is_collective(name, long_name) is yes


def test_round_collective_ms_reads_collectives_inside_rounds(tmp_path,
                                                             monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", _write(tmp_path))
    read = spec.metric_reader("round_collective_ms")
    # all-reduce.1 0.2 + the all-gather's start 0.1 and done 0.05 + the
    # second round's all-reduce.3 0.3; not the fusion that reads an
    # all-reduce, not the prefill step's all-reduce.2
    assert read(SimpleNamespace(trace=object())) == pytest.approx(0.65 / 2)
    assert read(SimpleNamespace(trace=None)) is None


def test_round_collective_ms_is_none_without_a_tpu_plane(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", _write(tmp_path, device=False))
    assert spec.metric_reader("round_collective_ms")(
        SimpleNamespace(trace=object())) is None


def _ctx(**kw):
    t = SimpleNamespace(kv_bytes_per_token=KV_TARGET)
    d = SimpleNamespace(kv_bytes_per_token=KV_DRAFTER)
    base = dict(trace=object(), traced_rounds=[[100, 200], [300]],
                counts=(t, d), gamma=3, peaks={"hbm_bytes_per_s": 4 * 819e9})
    return SimpleNamespace(**{**base, **kw})


def test_paged_attn_hbm_share_by_hand(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", _write(tmp_path))
    read = spec.metric_reader("paged_attn_hbm_share")
    # kernel leaves in the rounds: 1.0 + 0.5 ms over 2 rounds (not the
    # prefill step's); 300 live tokens a traced round on average
    nbytes = 300 * (KV_TARGET + 4 * KV_DRAFTER)
    want = 100 * nbytes / (0.75e-3 * 4 * 819e9)
    assert read(_ctx()) == pytest.approx(want)
    assert 0 < want < 100
    assert read(_ctx(trace=None)) is None
    assert read(_ctx(traced_rounds=[])) is None
    assert read(_ctx(peaks={})) is None          # no chip, no peak


def test_paged_attn_hbm_share_is_none_without_the_kernel_scope(
        tmp_path, monkeypatch):
    """An older program (no ``paged_kernel`` scope) reads nothing."""
    old = [(n, a, b, tf.replace("/paged_kernel", ""), ln)
           for n, a, b, tf, ln in OPS]
    monkeypatch.setattr(scopes, "TRACE_DIR", _write(tmp_path, old))
    assert spec.metric_reader("paged_attn_hbm_share")(_ctx()) is None


# ------------------------------------------ a tiny cell on four devices

SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, sys.argv[1])
    import run
    from lib import scopes, spec

    bench_dir, bench, seed, trace = Path(sys.argv[2]), \\
        json.loads(sys.argv[3]), int(sys.argv[4]), sys.argv[5]
    seen = {}
    read_metrics = spec.read_metrics

    def spy(names, ctx, bench_dir):
        # the recorded TPU trace stands in for the CPU's, which has no
        # device plane, and one v5e's peaks for the CPU's, which has none
        scopes.TRACE_DIR = Path(trace)
        ctx.peaks = {"hbm_bytes_per_s": 4 * 819e9}
        seen["kv"] = [c.kv_bytes_per_token for c in ctx.counts]
        return read_metrics(names, ctx, bench_dir)

    spec.read_metrics = spy
    res, _ = run.run_cell("tiny-yi-quad", seed, 1.5, True,
                          bench_dir=bench_dir, benchmark=bench,
                          allow_cpu=True)
    print(json.dumps({"result": res, "kv": seen["kv"]}))
""")


def test_tiny_cell_across_four_devices_reads_both_metrics(tmp_path):
    d = tmp_path / "bench"
    shutil.copytree(BENCH / "tests" / "data" / "traffic", d / "traffic")
    shutil.copytree(BENCH / "metrics", d / "metrics")
    (d / "configs").mkdir()
    # Yi's head layout (32 / 4, so each device holds one KV head) at a toy
    # width
    config = spec.load_config("yi-9b")
    config.update(name="tiny-yi", hidden_size=64, intermediate_size=128,
                  head_dim=16, num_hidden_layers=2, vocab_size=512)
    config["drafter"] = dict(config["drafter"], num_hidden_layers=1,
                             hidden_size=32, head_dim=16,
                             intermediate_size=64)
    (d / "configs" / "tiny-yi.json").write_text(json.dumps(config))
    real = spec.load_benchmark()
    name = "tiny-yi-quad"
    bench = {"workloads": [{"name": name, "config": "tiny-yi",
                            "traffic": "tiny", "chips": 4, "why": "test"}],
             "end_to_end": [dict(m, workloads=[name])
                            for m in real["end_to_end"]],
             "per_layer": [dict(m, workloads=[name])
                           for m in real["per_layer"]]}
    trace = _write(tmp_path / "trace_in")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(BENCH), str(d), json.dumps(bench),
         str(SEED), str(trace)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert res["correct"] is True, res["check"]
    assert res["device"]["count"] == 4
    # every chip's heads counted: 2 layers x K and V x 4 heads x 16 x 2 B
    assert out["kv"] == [2 * 2 * 4 * 16 * 2, 1 * 2 * 4 * 16 * 2]
    for metric in ("round_collective_ms", "paged_attn_hbm_share"):
        assert res["metrics"][metric]["value"] is not None, metric
    assert res["metrics"]["round_collective_ms"]["value"] == \
        pytest.approx(0.65 / 2)
    assert 0 < res["metrics"]["paged_attn_hbm_share"]["value"] < 100
