"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  PYTHONPATH=src python -m benchmarks.run            # quick suite
  PYTHONPATH=src python -m benchmarks.run --full     # longer sweeps
  PYTHONPATH=src python -m benchmarks.run --only table1,fig3
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

ALL = ("table1", "fig5", "table3", "fig3", "fig4", "fig6", "fig8",
       "serving_scale", "ablation_teacher", "kernels", "roofline")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated subset")
    args = ap.parse_args(argv)
    quick = not args.full
    only = set(args.only.split(",")) if args.only else set(ALL)

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []

    per_frame = None
    if "table1" in only:
        try:
            from benchmarks.table1_schemes import run as t1

            _, per_frame = t1(quick=quick)
        except Exception:
            failures.append(("table1", traceback.format_exc()))
    if "fig5" in only:
        try:
            from benchmarks.fig5_cdf import run as f5

            f5(per_frame=per_frame, quick=quick)
        except Exception:
            failures.append(("fig5", traceback.format_exc()))
    for name, mod in (("table3", "table3_selection"), ("fig3", "fig3_asr"),
                      ("fig4", "fig4_bw_sweep"), ("fig6", "fig6_multiclient"),
                      ("fig8", "fig8_horizon"),
                      ("serving_scale", "serving_scale"),
                      ("ablation_teacher", "ablation_teacher"),
                      ("kernels", "kernels_bench"),
                      ("roofline", "roofline_report")):
        if name not in only:
            continue
        try:
            module = __import__(f"benchmarks.{mod}", fromlist=["run"])
            module.run(quick=quick)
        except Exception:
            failures.append((name, traceback.format_exc()))

    print(f"# total {time.time()-t0:.1f}s, {len(failures)} failures", file=sys.stderr)
    for name, tb in failures:
        print(f"# FAILED {name}\n{tb}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    main()
