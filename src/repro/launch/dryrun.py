import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # a CPU compile tool: never take a chip

"""Multi-pod dry-run: lower + compile every (arch x input-shape) step on the
production meshes, print memory/cost analysis, and emit roofline terms.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape decode_32k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out experiments/dryrun
  python -m repro.launch.dryrun --all --subprocess ...   # isolation driver

The 512-placeholder-device XLA flag above MUST precede every other import
(jax locks the device count on first init) and must never leak into smoke
tests or benches — hence dryrun-only. The CPU platform is pinned next to
it, for this process and the ``--all`` children that inherit the
environment, so a dry run on a machine with a TPU never holds the chip."""

import argparse
import json
import subprocess
import sys
import time
from functools import partial

import jax

from ..configs import ARCHS, ASSIGNED, INPUT_SHAPES, get_config
from ..configs.base import TrainConfig
from ..models.model import Model
from ..sharding import context
from ..training.pretrain import make_train_step
from .mesh import make_production_mesh, mesh_axes
from .roofline import analyze
from .specs import input_specs


def build_lowered(cfg, shape, mesh, tc=None, profile="baseline"):
    """jit-lower the step for (cfg, shape) with baseline shardings."""
    model = Model(cfg)
    tc = tc or TrainConfig()
    long_ctx = shape.name == "long_500k"
    daxes, maxis = mesh_axes(mesh)
    context.set_mesh(mesh, daxes, maxis, profile=profile)
    sp = input_specs(cfg, shape, mesh, tc, long_context=long_ctx)
    if shape.kind == "train":
        step = make_train_step(model, tc)
        jitted = jax.jit(step)
        return jitted.lower(sp["state"], sp["tokens"], sp["labels"])
    if shape.kind == "prefill":
        fn = partial(_prefill, model, shape.seq_len)
        jitted = jax.jit(fn)
        return jitted.lower(sp["params"], sp["tokens"])
    fn = partial(_decode, model, long_ctx)
    jitted = jax.jit(fn, donate_argnums=(3,))
    return jitted.lower(sp["params"], sp["tokens"], sp["positions"], sp["cache"])


def _prefill(model, cache_len, params, tokens):
    return model.prefill(params, tokens, cache_len=cache_len)


def _decode(model, long_ctx, params, tokens, positions, cache):
    return model.decode_step(params, tokens, positions, cache,
                             long_context=long_ctx)


def run_one(arch: str, shape_name: str, multi_pod: bool, tc=None,
            profile: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, tc, profile=profile)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_info = {"argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                    "output_bytes": getattr(mem, "output_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                    "peak_bytes": getattr(mem, "peak_memory_in_bytes", None)}
    except Exception as e:                      # CPU backend may not support
        mem_info = {"error": str(e)[:200]}
    try:
        cost = compiled.cost_analysis()
    except Exception as e:
        cost = {"error": str(e)[:200]}
    hlo = compiled.as_text()
    result = analyze(cfg, shape, cost, hlo, chips,
                     long_context=(shape.name == "long_500k"), profile=profile)
    result.update({"profile": profile,
                   "multi_pod": multi_pod, "mesh": dict(mesh.shape),
                   "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
                   "memory": mem_info, "ok": True})
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run all assigned arch x shape combos via subprocesses")
    ap.add_argument("--profile", choices=("baseline", "optimized"),
                    default="baseline")
    ap.add_argument("--out", default=None, help="write JSON result(s) here")
    ap.add_argument("--hlo-out", default=None, help="dump post-SPMD HLO text")
    args = ap.parse_args()

    if args.all:
        results = []
        for arch in ASSIGNED:
            for shape in INPUT_SHAPES:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--profile", args.profile]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                t0 = time.time()
                proc = subprocess.run(cmd, capture_output=True, text=True)
                ok = proc.returncode == 0
                line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "{}"
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    rec = {}
                if not ok:
                    rec = {"arch": arch, "shape": shape, "ok": False,
                           "multi_pod": args.multi_pod,
                           "error": proc.stderr[-2000:]}
                rec.setdefault("wall_s", round(time.time() - t0, 1))
                results.append(rec)
                status = "OK " if rec.get("ok") else "FAIL"
                print(f"[{status}] {arch:>22s} x {shape:<12s} "
                      f"{rec.get('compile_s', '?')}s compile "
                      f"bottleneck={rec.get('bottleneck', '-')}", file=sys.stderr)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            suffix = "multipod" if args.multi_pod else "singlepod"
            if args.profile != "baseline":
                suffix += "_" + args.profile
            with open(os.path.join(args.out, f"dryrun_{suffix}.json"), "w") as f:
                json.dump(results, f, indent=1)
        n_ok = sum(1 for r in results if r.get("ok"))
        print(f"{n_ok}/{len(results)} combos compiled", file=sys.stderr)
        sys.exit(0 if n_ok == len(results) else 1)

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    result = run_one(args.arch, args.shape, args.multi_pod,
                     profile=args.profile)
    if args.hlo_out:
        cfg = get_config(args.arch)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        lowered = build_lowered(cfg, INPUT_SHAPES[args.shape], mesh)
        with open(args.hlo_out, "w") as f:
            f.write(lowered.compile().as_text())
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
