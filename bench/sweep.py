"""Knee sweep of an open-loop cell: the same mix at several fixed rates.

    python3 bench/sweep.py --workload phi4-chat --seed 11 --seconds 20 \
        --rates 2,3,4,5

One process builds the weights and warms up once, then serves the mix at
each rate through a fresh engine and prints one JSON line per rate: TTFT
p90 and TPOT p50/p90 of the requests that finished within the drain cap,
how many did not, and the scheduler's queue depth at the window's start,
middle and end (a queue that grows through the window is above the knee).
No correctness check is made.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain-cap", type=float, default=15.0)
    args = ap.parse_args(argv)
    run.configure_jax()
    import jax
    from lib import stats, traffic, weights
    from lib.draw import base_key
    from lib.driver import EngineTracer, run_window

    c = run.prepare(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    with run.serving_mesh(c.mesh):
        params = weights.build(c.models, base_key(args.seed), c.mesh)
        jax.block_until_ready(params)
        run.warm_up(c.models, params, c.serving, c.mix)
        for rate in rates:
            mix = dict(c.mix, rate_per_s=rate, drain_cap_s=args.drain_cap)
            reqs = traffic.generate(mix, args.seed, args.seconds,
                                    c.dims[0]["vocab_size"])
            tracer = EngineTracer()
            engine = run.make_engine(c.models, params, c.serving, tracer)
            depth = {}
            marks = {"start": mix["ramp_s"],
                     "mid": mix["ramp_s"] + args.seconds / 2,
                     "end": mix["ramp_s"] + args.seconds}

            def on_tick(t, rec, engine=engine, depth=depth):
                for name, at in marks.items():
                    if name not in depth and t >= at:
                        depth[name] = len(engine.scheduler)

            rec = run_window(engine, reqs, mix, args.seconds, tracer, on_tick)
            ttft, tpot = stats.latencies(rec)
            out = {"workload": args.workload, "rate_per_s": rate,
                   "counted": len(rec.counted), "finished": len(ttft),
                   "tokens_per_s": rec.window_tokens / args.seconds,
                   "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                   "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
                   "tpot_p50_ms": 1e3 * stats.percentile(tpot, 50),
                   "tpot_p90_ms": 1e3 * stats.percentile(tpot, 90),
                   "queue_depth": depth}
            print(json.dumps(out), flush=True)
            del engine
            gc.collect()


if __name__ == "__main__":
    main()
