"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table1]

Each module exposes ``run() -> list[dict]``; rows are printed as CSV with a
leading `bench` column.  Besides the CSV, a machine-readable
``BENCH_summary.json`` records which benches ran, whether they passed,
their wall seconds, and a headline row each — ``scripts/bench_report.py``
folds it into the trajectory report.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.common import emit

BENCHES = ["fig1_gradient", "fig2_finite_sum", "fig3_stochastic",
           "fig4_dnn", "fig5_quadratic_pl", "table1_complexity",
           "kernel_bench", "compress_bench", "driver_bench",
           "fed_bench", "fed_scale_bench", "fed_async_bench",
           "fed_faults_bench"]


def _headline(rows) -> dict:
    """The first row's scalar fields — a stable one-line digest of what
    the bench measured (full rows stay in the CSV / BENCH_*.json)."""
    if not rows:
        return {}
    return {k: v for k, v in rows[0].items()
            if isinstance(v, (int, float, str, bool)) and v != ""}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (prefix match)")
    ap.add_argument("--summary", default="BENCH_summary.json",
                    help="machine-readable run summary path ('' disables)")
    args = ap.parse_args(argv)
    selected = BENCHES
    if args.only:
        pats = args.only.split(",")
        selected = [b for b in BENCHES
                    if any(b.startswith(p) for p in pats)]
    failures = 0
    summary = []
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        print(f"\n=== {name} ===")
        try:
            rows = mod.run()
            emit(rows)
            dt = time.time() - t0
            print(f"[{name}] done in {dt:.1f}s")
            summary.append({"name": name, "ok": True,
                            "seconds": round(dt, 1), "rows": len(rows),
                            "headline": _headline(rows)})
        except Exception as e:
            failures += 1
            print(f"[{name}] FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            summary.append({"name": name, "ok": False,
                            "seconds": round(time.time() - t0, 1),
                            "rows": 0,
                            "error": f"{type(e).__name__}: {e}"})
    if args.summary:
        with open(args.summary, "w") as f:
            json.dump({"benches": summary, "failures": failures}, f,
                      indent=2)
            f.write("\n")
        print(f"\n[run] wrote {args.summary} "
              f"({len(summary)} benches, {failures} failed)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
