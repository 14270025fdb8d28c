"""A cell across chips, on four virtual CPU devices in a subprocess (the
device count must not leak into this test session): the tiny cell served
as ``chips: 4`` comes out correct, with its weights drawn straight into the
program's shardings, the same values as on one device, and memory read per
chip. A cell of one chip takes no mesh."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from lib import spec  # noqa: E402

SEED = 2 ** 33 + 29

SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    import jax
    import numpy as np
    from jax.tree_util import DictKey
    sys.path.insert(0, sys.argv[1])
    import run
    from lib import weights
    from lib.draw import base_key
    from repro.sharding import context

    bench_dir, bench, seed = Path(sys.argv[2]), json.loads(sys.argv[3]), \\
        int(sys.argv[4])
    seen = {"engine_mesh": []}
    build = weights.build

    def spy_build(models, key, mesh=None):
        seen["mesh"] = mesh
        seen["params"] = build(models, key, mesh)
        return seen["params"]

    make_engine = run.make_engine

    def spy_make_engine(*a, **k):
        seen["engine_mesh"].append(context.get_mesh())
        return make_engine(*a, **k)

    weights.build, run.make_engine = spy_build, spy_make_engine
    res, lines = run.run_cell("tiny-quad", seed, 1.5, False,
                              bench_dir=bench_dir, benchmark=bench,
                              allow_cpu=True)
    mesh = seen["mesh"]
    c = run.prepare("tiny-quad", bench_dir, bench, allow_cpu=True)
    one = build(c.models, base_key(seed))

    def spec_of(specs, path):
        for p in path:
            specs = specs[p.key if isinstance(p, DictKey) else p.idx]
        return specs

    same, tp_spread, tp_leaves = True, True, 0
    for (model, _, _), served, alone in zip(c.models, seen["params"], one):
        _, specs = weights.layout(model)
        for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
            other = spec_of(alone, path)
            same &= (leaf.dtype == other.dtype and np.asarray(leaf).tobytes()
                     == np.asarray(other).tobytes())
            names = spec_of(specs, path)
            if "tp" in names:
                tp_leaves += 1
                dim = names.index("tp")
                shard = leaf.addressable_shards[0].data.shape
                tp_spread &= (len(leaf.sharding.device_set) == 4
                              and shard[dim] * 4 == leaf.shape[dim])
    print(json.dumps({
        "result": res, "mesh_shape": dict(mesh.shape),
        "window": [line for line in lines if line.startswith("window:")],
        "mesh_axes": list(mesh.axis_names),
        "engine_mesh": [m is mesh for m in seen["engine_mesh"]],
        "after": context.get_mesh() is None, "same": bool(same),
        "tp_spread": bool(tp_spread), "tp_leaves": tp_leaves}))
""")


def _bench_dir(d: Path, chips: int):
    shutil.copytree(BENCH / "tests" / "data" / "configs", d / "configs")
    shutil.copytree(BENCH / "tests" / "data" / "traffic", d / "traffic")
    shutil.copytree(BENCH / "metrics", d / "metrics")
    real = spec.load_benchmark()
    name = "tiny-quad" if chips == 4 else "tiny-chat"
    return {"workloads": [{"name": name, "config": "tiny", "traffic": "tiny",
                           "chips": chips, "why": "test"}],
            "end_to_end": [dict(m, workloads=[name])
                           for m in real["end_to_end"]],
            "per_layer": [dict(m, workloads=[name])
                          for m in real["per_layer"]]}


def test_cell_across_four_chips(tmp_path):
    bench = _bench_dir(tmp_path, 4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(BENCH), str(tmp_path),
         json.dumps(bench), str(SEED)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0
    # the warm-up covers the programs the window runs across chips too
    assert "; 0 compiles in the window" in out["window"][0]
    assert res["device"]["count"] == 4
    assert res["device"]["memory_peak_bytes_per_chip"] == [None] * 4
    assert out["mesh_shape"] == {"data": 1, "model": 4}
    assert out["mesh_axes"] == ["data", "model"]
    # the warm-up engine and the served one, both built inside the mesh;
    # the check after it, outside
    assert out["engine_mesh"] == [True, True] and out["after"]
    assert out["same"] and out["tp_spread"]
    assert out["tp_leaves"] >= 2 * 7     # wq wk wv wo and the MLP, each model


def test_one_chip_takes_no_mesh(tmp_path):
    from repro.sharding import context
    bench = _bench_dir(tmp_path, 1)
    c = run.prepare("tiny-chat", tmp_path, bench, allow_cpu=True)
    assert c.mesh is None and len(c.devices) == 1
    with run.serving_mesh(c.mesh):
        assert context.get_mesh() is None


def test_fewer_devices_than_the_cell_asks_for_is_refused(tmp_path):
    bench = _bench_dir(tmp_path, 4)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.prepare("tiny-quad", tmp_path, bench, allow_cpu=True)
