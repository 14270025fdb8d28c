"""Readings that the cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload phi4-chat --seeds 1,2,3 --seconds 15

For each seed, in one process: a run of the cell (``run.run_cell``) with the
control in the program's place: the same reference computed in float8
(e4m3, per-channel scales) makes the choices at the served decision points,
and the harness's own check judges them, so a sound control comes out
``correct: false``. The run reports the program's widest gap over the same
requests beside it. Prints one JSON line per seed. The limit in the cell's
traffic file lies above the program's readings and below the control's.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, _ = run.run_cell(args.workload, seed, args.seconds, False,
                                 control=True)
        check = result["check"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"],
            "program_gap": check["program_gap"]["value"],
            "control_gap": check["widest_gap"]["value"],
            "limit": check["widest_gap"]["limit"]}), flush=True)
        del result
        gc.collect()


if __name__ == "__main__":
    main()
