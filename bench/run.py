"""Chip benchmark of the continuous speculative server: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a model configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). One run:

  1. set-up: weights for target and drafter made on the device from the
     seed in one jitted call, a throwaway engine of the cell's shapes driven
     through every slot, the per-shape eager programs of the mix's prompt
     lengths and page counts compiled, then the served engine built;
  2. the window: the mix's requests through ``ContinuousEngine.submit`` /
     ``step``, a ramp, ``--seconds`` measured, then a drain of the counted
     requests while arrivals go on; with ``--trace 1`` a few seconds from
     its middle are profiled;
  3. the check: a sample of finished requests drawn from the seed, the
     longest among them, replayed against the plain float32 reference
     (``lib/replay.py``); ``correct`` holds if the widest gap is within the
     cell's limit.

A cell of ``chips`` > 1 is served across its first ``chips`` devices: a
(1, chips) mesh of the axes ("data", "model") that the program's sharding
rules map, the weights drawn straight into the program's shardings, and
set-up and window inside ``repro.sharding.context.use_mesh``; the check
runs on the first chip, outside the mesh. A cell of one chip takes no mesh.
Memory and the trace are read per chip.

The last stdout line is the result JSON. The run exits non-zero, printing
no result, without a TPU or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
#: seconds of the window that a traced run profiles, around its middle
TRACE_SECONDS = 4.0
#: the reference's logit rows per sample are padded to a multiple of this
ROW_BUCKET = 128


def _log(lines, msg):
    """Keep a line for the caller and print it now, so that a run cut by
    its time limit still shows how far it got."""
    lines.append(msg)
    print(f"[{time.perf_counter() - T_PROCESS:8.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _paths():
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


_paths()

import numpy as np  # noqa: E402

from lib import counts, spec, stats, tracing, traffic as traffic_mod  # noqa: E402
from lib.driver import EngineTracer, run_window  # noqa: E402
from lib.peaks import peaks  # noqa: E402


@dataclass
class Context:
    """What the per-layer metric readers (``metrics/*.py``) read."""
    rec: object
    stats: dict
    compile_s: float
    trace: object
    traced_rounds: list
    counts: tuple
    gamma: int
    peaks: dict          # the cell's chips together


def program_models(config: dict):
    """The program's ModelConfig/Model for target and drafter."""
    from repro.configs.base import ModelConfig
    from repro.models.model import Model
    out = []
    for dims, name in zip(spec.target_and_drafter(config),
                          ("target", "drafter")):
        cfg = ModelConfig(
            name=f"{config['name']}-{name}", arch_type="dense",
            num_layers=dims["num_layers"], d_model=dims["d_model"],
            num_heads=dims["num_heads"], num_kv_heads=dims["num_kv_heads"],
            d_ff=dims["d_ff"], vocab_size=dims["vocab_size"],
            head_dim=dims["head_dim"], rope_theta=dims["rope_theta"],
            norm_eps=dims["norm_eps"], tie_embeddings=dims["tie_embeddings"],
            dtype=dims["dtype"], param_dtype=dims["dtype"], remat=False)
        out.append((Model(cfg), dims, name))
    return out


def make_engine(models, params, serving: dict, tracer):
    from repro.core.speculative import SDConfig
    from repro.serving import ContinuousEngine
    (tm, _, _), (dm, _, _) = models
    return ContinuousEngine(
        target=tm, target_params=params[0], draft=dm, draft_params=params[1],
        sd=SDConfig(gamma=serving["gamma"],
                    temperature=serving["temperature"],
                    top_p=serving["top_p"]),
        max_batch=serving["max_batch"], max_seq_len=serving["max_seq_len"],
        page_size=serving["page_size"], num_pages=serving["num_pages"],
        prefill_chunk=serving["prefill_chunk"], tracer=tracer)


def warm_up(models, params, serving: dict, mix: dict, lines=None):
    """Compile, before the window, every program the window runs: a
    throwaway engine of the cell's shapes takes a request in every slot
    through prefill, decode rounds and retirement; then the engine's eager
    per-shape programs run once for every shape the mix can bring: the
    prompt copy into the token buffer (one program per prompt length) and
    the page invalidation at retirement (one per page count). The prompt
    copy also runs on the engine's state as made, before any round: across
    chips a round returns that state laid out otherwise, and the first
    prompts of the window meet the state as made."""
    import jax
    import jax.numpy as jnp
    from repro.serving import ServeRequest
    from repro.serving.kv_pool import invalidate_pages
    lines = [] if lines is None else lines
    engine = make_engine(models, params, serving, EngineTracer())
    pairs = traffic_mod.shapes(mix)
    plens = sorted({p for p, _ in pairs})

    def copy_prompts(tokens):
        for n in plens:
            tokens.at[0, :n].set(jnp.zeros((n,), jnp.int32))
        jax.block_until_ready(tokens)
    copy_prompts(engine._state["tokens"])
    g, c = serving["gamma"], serving["prefill_chunk"]
    for i in range(serving["max_batch"]):
        # the first prompt spans two chunks: a chunk at a non-zero offset
        engine.submit(ServeRequest(
            prompt=np.full((c + 1 if i == 0 else 2,), 1, np.int32),
            max_new_tokens=3 * (g + 1), request_id=i))
    while engine.has_work():
        engine.step()
    _log(lines, "warm-up: every slot served")
    st = engine._state
    copy_prompts(st["tokens"])
    _log(lines, f"warm-up: {len(plens)} prompt lengths")
    pages = sorted({engine.pool.pages_needed(engine._worst_case_tokens(
        ServeRequest(prompt=np.zeros((p,), np.int32), max_new_tokens=m)))
        for p, m in pairs})
    for n in pages:
        ids = list(range(1, n + 1))
        for pool in (st["t_cache"], st["d_cache"]):
            invalidate_pages(pool, ids)
    jax.block_until_ready(st)
    _log(lines, f"warm-up: {len(pages)} page counts")
    del engine, st
    gc.collect()


def check_sample(rec, reqs, tracer, seed: int, min_tokens: int):
    """Finished counted requests to replay: the longest, then others drawn
    from the seed until ``min_tokens`` served tokens."""
    done = [rid for rid in rec.counted if rid in rec.finish]
    if not done:
        return []
    size = {rid: len(reqs[rid].prompt) + rec.n_new[rid] for rid in done}
    longest = max(done, key=lambda r: (size[r], -r))
    rng = np.random.default_rng(seed)
    order = [longest] + [int(r) for r in rng.permutation(
        [r for r in done if r != longest])]
    picked, n = [], 0
    for rid in order:
        picked.append(rid)
        n += rec.n_new[rid]
        if n >= min_tokens:
            break
    return [rec.sample(rid, reqs[rid].prompt, tracer.slot[rid])
            for rid in sorted(picked)]


def reference_gaps(samples, dims, key, serving: dict, control=False):
    """Widest decision gap of each sample (and of the control, the float8
    reference in the program's place, where ``control``). -> list of (gap,
    control gap or None, decisions)."""
    from lib.reference import RefModel
    from lib.replay import log_probs, sample_gaps, wanted_positions
    seqs = [np.concatenate([s.prompt, s.served]) for s in samples]
    # rows padded to a multiple of ROW_BUCKET, with gamma + 1 to spare for
    # the replay's fixed-size reads: few shapes, so few compiles
    want = []
    for s in samples:
        w = wanted_positions(s)
        n = -(-(len(w) + serving["gamma"] + 1) // ROW_BUCKET) * ROW_BUCKET
        want.append(np.concatenate([w, np.full(n - len(w), w[-1])]))
    inv_t = 1.0 / serving["temperature"]
    models = [RefModel(d, tag) for d, tag in zip(dims, ("target", "drafter"))]
    hid = [m.hidden(key, seqs, want) for m in models]
    heads = [m.head(key) for m in models]
    low = low_hid = low_heads = None
    if control:
        low = [RefModel(d, tag, fp8=True)
               for d, tag in zip(dims, ("target", "drafter"))]
        low_hid = [m.hidden(key, seqs, want) for m in low]
        low_heads = [m.head(key) for m in low]
    out = []
    for i, s in enumerate(samples):
        lt, ld = (log_probs(m.logits(h, hs[i]), inv_t)
                  for m, h, hs in zip(models, heads, hid))
        lt_low = ld_low = None
        if control:
            lt_low, ld_low = (log_probs(m.logits(h, hs[i]), inv_t)
                              for m, h, hs in zip(low, low_heads, low_hid))
        out.append(sample_gaps(s, lt, ld, serving["max_batch"],
                               serving["gamma"], lt_low, ld_low))
    return out


def device_info(chips: int, allow_cpu: bool):
    """The cell's chips: the first ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"bench: no TPU found (JAX's first device is "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def cell_mesh(devices):
    """None for one chip; else the (1, chips) mesh of the axes the
    program's sharding rules name, tensor parallel over "model"."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(devices).reshape(1, len(devices)), ("data", "model"))


def serving_mesh(mesh):
    """The program's launch contract for a mesh (``use_mesh``); nothing for
    one chip."""
    if mesh is None:
        return contextlib.nullcontext()
    from repro.sharding.context import use_mesh
    return use_mesh(mesh, (), "model")


def memory_peaks(devices):
    """Peak bytes in use on each chip; None where the backend reports
    none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


@dataclass
class Prepared:
    """A cell's files, its chips and mesh, and the program's models."""
    benchmark: dict
    cell: dict
    config: dict
    mix: dict
    serving: dict
    check: dict
    devices: list
    mesh: object
    models: list
    dims: list


def prepare(workload: str, bench_dir: Path = BENCH, benchmark=None,
            allow_cpu=False) -> Prepared:
    benchmark = benchmark or spec.load_benchmark()
    cell = spec.find_cell(benchmark, workload)
    config = spec.load_config(cell["config"], bench_dir)
    mix = spec.load_traffic(cell["traffic"], bench_dir)
    serving = mix["serving"]
    if serving["top_p"] != 1.0 or serving["temperature"] <= 0:
        raise ValueError("the replay check handles temperature > 0 with "
                         "top_p 1.0 only")
    devices = device_info(cell["chips"], allow_cpu)
    models = program_models(config)
    return Prepared(benchmark, cell, config, mix, serving, mix["check"],
                    devices, cell_mesh(devices), models,
                    [m[1] for m in models])


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: Path = BENCH, benchmark=None, allow_cpu=False,
             fault=None, control=False, t_process: float = T_PROCESS):
    """One run of one cell -> (result dict, lines for stderr). ``fault``,
    for tests, receives the served engine before the window and may break
    it. With ``control`` the check judges the control's choices (the float8
    reference put in the program's place) instead of the served ones, and
    reports the program's widest gap beside them."""
    import jax
    from repro.analysis.recompile import CompileWatcher
    from lib import weights
    from lib.draw import base_key

    c = prepare(workload, bench_dir, benchmark, allow_cpu)
    benchmark, mix, serving, check = c.benchmark, c.mix, c.serving, c.check
    devices, models, dims = c.devices, c.models, c.dims
    dev = devices[0]
    vocab = dims[0]["vocab_size"]
    lines = []
    key = base_key(seed)
    ramp = mix["ramp_s"]
    mid = ramp + seconds / 2
    profile = {"on": False, "done": False, "ann": None, "t": None}

    def on_tick(t, rec):
        if not trace or profile["done"]:
            return
        if not profile["on"] and t >= mid - TRACE_SECONDS / 2:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            profile["ann"] = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            profile["ann"].__enter__()
            profile["on"], profile["t"] = True, [t, None]
        elif profile["on"] and t >= mid + TRACE_SECONDS / 2:
            profile["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            profile["on"], profile["done"] = False, True
            profile["t"][1] = t

    with serving_mesh(c.mesh):
        # ------------------------------------------------------ set-up
        with CompileWatcher() as setup_clock:
            t0 = time.perf_counter()
            params = weights.build(models, key, c.mesh)
            jax.block_until_ready(params)
            t_weights = time.perf_counter() - t0
            reqs = traffic_mod.generate(mix, seed, seconds, vocab)
            t0 = time.perf_counter()
            _log(lines, f"weights: {t_weights:.3f}s")
            warm_up(models, params, serving, mix, lines)
            t_warm = time.perf_counter() - t0
            tracer = EngineTracer(annotate=trace)
            engine = make_engine(models, params, serving, tracer)
            if fault is not None:
                fault(engine)
        setup_s = time.perf_counter() - t_process
        _log(lines, f"setup: {setup_s:.3f}s (weights {t_weights:.3f}s, "
                     f"warm-up {t_warm:.3f}s, compile "
                     f"{setup_clock.seconds:.3f}s in "
                     f"{setup_clock.n_compiles} compiles)")

        # ------------------------------------------------------ window
        with CompileWatcher() as window_clock:
            rec = run_window(engine, reqs, mix, seconds, tracer, on_tick)
        if profile["on"]:
            on_tick(float("inf"), rec)
    per_chip = memory_peaks(devices)
    peak = max((p for p in per_chip if p is not None), default=None)
    _log(lines, f"window: {len(rec.counted)} counted requests, "
                 f"{rec.window_tokens} tokens in {seconds}s; "
                 f"{window_clock.n_compiles} compiles in the window "
                 f"{window_clock.signatures}; generator late "
                 f"max {1e3 * max(rec.late.values(), default=0):.1f}ms; "
                 f"drain ended at {rec.drain_end:.1f}s; memory peak per "
                 f"chip {per_chip}")

    failed = sum(1 for rid in rec.counted if rid not in rec.finish)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak,
              "memory_peak_bytes_per_chip": per_chip}
    result = {"correct": False, "attempted": len(rec.counted),
              "failed": failed, "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in
             benchmark["end_to_end"] + benchmark["per_layer"]}
    wanted = [m["name"] for m in spec.cell_metrics(benchmark, workload,
                                                    trace)]
    if not trace:
        e2e = stats.end_to_end(rec, setup_s)
        values = {n: e2e[n] for n in wanted}
    else:
        tr = (tracing.load(str(TRACE_DIR), {d.id for d in devices})
              if profile["done"] else None)
        lo, hi = profile["t"] or (0, 0)
        traced = [[c for _, c in rows] for t, rows in rec.rounds
                  if lo <= t < hi and rows]
        chip = peaks(dev.device_kind) if dev.platform == "tpu" else {}
        ctx = Context(rec, engine.stats, setup_clock.seconds, tr, traced,
                      (counts.model_counts(params[0], engine._state["t_cache"]),
                       counts.model_counts(params[1], engine._state["d_cache"])),
                      serving["gamma"],
                      {k: v * len(devices) for k, v in chip.items()})
        values = spec.read_metrics(wanted, ctx, bench_dir)
        if tr is not None:
            device["busy_s"] = tracing.busy_s(tr)
            device["window_s"] = tr.window_s
            result["breakdown"] = tracing.breakdown(tr)
            _log(lines, f"trace: round program {tracing.round_program(tr)} "
                        f"{tracing.round_time(tr)}, prefill "
                        f"{tracing.prefill_time(tr)}, "
                        f"{len(tr.modules)} program runs")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for n, v in values.items():
        if v is not None:
            result["metrics"][n] = {"value": float(v), "unit": units[n]}

    # ---------------------------------------------------------- check
    samples = check_sample(rec, reqs, tracer, seed, check["min_tokens"])
    del engine, params
    gc.collect()
    t0 = time.perf_counter()
    gaps = (reference_gaps(samples, dims, key, serving, control)
            if samples else [])
    widest = max((cg if control else g for g, cg, _ in gaps),
                 default=float("inf"))
    full = all(len(s.served) == reqs[s.rid].max_new
               and s.served.min() >= 0 and s.served.max() < vocab
               for s in samples)
    result["correct"] = bool(samples) and full and widest <= check["max_gap"]
    _log(lines, f"check: {len(samples)} requests, "
                 f"{sum(len(s.served) for s in samples)} served tokens, "
                 f"{sum(n for _, _, n in gaps)} decisions, reference "
                 f"{time.perf_counter() - t0:.1f}s; gaps "
                 f"{[round(cg if control else g, 6) for g, cg, _ in gaps]}")
    result["check"] = {"widest_gap": {
        "value": widest if np.isfinite(widest) else None,
        "limit": check["max_gap"]}}
    if control:
        program = max((g for g, _, _ in gaps), default=None)
        result["check"]["program_gap"] = {"value": program,
                                          "limit": check["max_gap"]}
        _log(lines, f"control: program_gap={program!r}")
    _log(lines, f"widest_gap={widest!r} limit={check['max_gap']!r}")
    return result, lines


def configure_jax():
    """Persistent compilation cache inside the checkout, at a fixed path,
    for every program the run compiles, however small."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure_jax()
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
