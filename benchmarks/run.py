"""Benchmark harness entry point: one section per paper table/figure plus
the roofline/dry-run and kernel suites. Prints ``name,value,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-repro] [--smoke]
      [--json [DIR]] [--compare] [--compare-tol T]

--quick shrinks the repro pipeline (CI-scale); without a cached
experiments/repro_results.json the full pipeline (~10 min CPU) runs once and
is cached for subsequent invocations.

--smoke is the CI registration gate: every non-repro section runs at tiny
shapes. In every mode a section error is reported as a CSV row, the
remaining sections still run, and the process exits 1 at the end — so a
benchmark whose imports or registrations rot cannot pass silently.

--json [DIR] persists each section's numeric rows as one run record in
DIR/BENCH_<section>.json (bounded trajectory, default DIR "."); --compare
then gates the fresh run against the previous same-config record and exits
nonzero when a direction-aware metric regressed by more than --compare-tol
(relative, default 0.25). See benchmarks.bench_persist.

Every section also emits a ``<section>_section_wall_s`` row — harness wall
time, informational only (never gates a compare).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-repro", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR", dest="json_dir",
                    help="persist per-section BENCH_<section>.json run "
                         "records into DIR (default '.')")
    ap.add_argument("--compare", action="store_true",
                    help="with --json: compare against the previous "
                         "same-config run; exit nonzero on regression")
    ap.add_argument("--compare-tol", type=float, default=0.25,
                    help="relative regression tolerance for --compare")
    args = ap.parse_args()
    if args.compare and args.json_dir is None:
        ap.error("--compare requires --json (needs a trajectory to compare "
                 "against)")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    smoke = args.smoke
    quick = args.quick or smoke
    skip_repro = args.skip_repro or smoke

    from . import (table1_configs, roofline_report, kernels_bench,
                   serving_bench, spectree_bench, quant_bench,
                   draftheads_bench, quality_bench)

    sections = [("table1", lambda: table1_configs.rows())]
    if not skip_repro:
        from . import fig1_mbsu, fig2_checkpoints, fig3_ood
        sections += [
            ("fig1", lambda: fig1_mbsu.rows(quick=quick)),
            ("fig2", lambda: fig2_checkpoints.rows(quick=quick)),
            ("fig3", lambda: fig3_ood.rows(quick=quick)),
        ]
    sections += [
        ("roofline", roofline_report.rows),
        ("kernels", kernels_bench.rows),
        # --smoke also turns on the engine's sanitize mode: the paged-KV
        # invariant sweep (pool accounting, host/device page-table mirror,
        # COW aliasing) runs every few rounds and raises on violation
        ("serving", lambda: serving_bench.rows(quick=quick, sanitize=smoke)),
        ("traffic", lambda: serving_bench.traffic_rows(quick=quick,
                                                       sanitize=smoke)),
        ("spectree", lambda: spectree_bench.rows(quick=quick)),
        ("quant", lambda: quant_bench.rows(quick=quick)),
        ("draftheads", lambda: draftheads_bench.rows(quick=quick)),
        ("quality", lambda: quality_bench.rows(quick=quick)),
    ]

    run_config = {"quick": quick, "smoke": smoke}
    failed, regressions = [], []
    print("name,value,derived")
    for name, fn in sections:
        t0 = time.perf_counter()
        try:
            rows = list(fn())
        except Exception as e:  # report, run the rest, fail at the end
            print(f"{name}_ERROR,0,{type(e).__name__}: {str(e)[:120]}")
            failed.append(name)
            rows = []
        wall_s = time.perf_counter() - t0
        rows.append((f"{name}_section_wall_s", round(wall_s, 3), ""))
        for row in rows:
            print(",".join(str(x) for x in row))
        if args.json_dir is not None:
            from .bench_persist import (append_run, compare_run,
                                        load_history, record)
            rec = record(name, rows, wall_s, config=run_config)
            if args.compare:
                history = load_history(args.json_dir, name)
                for metric, prev, cur, bad in compare_run(
                        history, rec, args.compare_tol):
                    print(f"REGRESSION,{bad:.3f},{name}.{metric} "
                          f"{prev:.6g} -> {cur:.6g}")
                    regressions.append((name, metric))
            append_run(args.json_dir, rec)
    if failed:
        print(f"SECTIONS_FAILED,{len(failed)},{';'.join(failed)}")
        sys.exit(1)
    if regressions:
        print(f"COMPARE_FAILED,{len(regressions)},"
              + ";".join(f"{s}.{m}" for s, m in regressions))
        sys.exit(2)


if __name__ == "__main__":
    main()
