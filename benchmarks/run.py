"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick] \
        [--json [PATH]]

Prints ``name,us_per_call,derived`` CSV lines.  --quick sets
REPRO_BENCH_QUICK=1, which suites honouring it (aqp_boxes, aqp_engine,
aqp_serve, aqp_restore, aqp_progressive, aqp_rff) read at run() time to
shrink to a CI-smoke configuration.  Every run also writes the
machine-readable report to BENCH_aqp.json at the repo root (--json PATH
overrides the destination): every emitted measurement with name,
us_per_call, p50/p99 when raw samples were provided, suite-specific extras
(speedups, batch depths), plus git sha, config, and wall time — CI archives
it and `scripts/validate_metrics.py --bench` schema-checks it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

SUITES = ("paper_validation", "plugin", "lscv_h", "lscv_H", "table3",
          "kernels", "aqp_batch", "aqp_boxes", "aqp_grouped", "aqp_engine",
          "aqp_rff",
          "aqp_serve", "aqp_restore", "aqp_progressive", "roofline",
          "serving")

# the always-on report lands at the repo root regardless of the cwd the
# harness was launched from, so CI archiving finds one canonical path
_DEFAULT_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_aqp.json")


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help=f"one of {SUITES}")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for CI smoke runs")
    ap.add_argument("--json", nargs="?", const=_DEFAULT_JSON,
                    default=_DEFAULT_JSON, metavar="PATH",
                    help="where to write the machine-readable report "
                         "(always written; default BENCH_aqp.json at the "
                         "repo root)")
    args = ap.parse_args()
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    suites = [args.only] if args.only else list(SUITES)

    from repro.compile_cache import use_compile_cache

    from . import common

    use_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    for s in suites:
        mod = __import__(f"benchmarks.bench_{s}", fromlist=["run"])
        print(f"# --- {s} ({time.time() - t0:.0f}s elapsed) ---", flush=True)
        mod.run()
    wall = time.time() - t0
    print(f"# total {wall:.0f}s", flush=True)

    if args.json:
        doc = {
            "git_sha": _git_sha(),
            "ts": time.time(),
            "config": {"quick": bool(args.quick), "suites": suites,
                       "argv": sys.argv[1:]},
            "wall_s": wall,
            "results": common.RESULTS,
        }
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"# wrote {len(common.RESULTS)} results -> {args.json}",
              flush=True)


if __name__ == "__main__":
    main()
