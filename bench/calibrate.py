#!/usr/bin/env python3
"""Readings that the correctness limits are set from: a cell run on many
seeds in one process (set-up paid per seed, compilation once), each run's
sampled answers compared twice, as the program's and with the reference
computed in bfloat16 in the program's place (the control).

    python3 bench/calibrate.py --workload tpch.refresh --seeds 1,2,3 \
        --seconds 8

Prints one JSON line per seed ({"program": {...}, "control": {...}}) and a
summary: the largest program reading and the smallest control reading of
every number.  `limits/<config>.json` holds limits set between the two, as
PERF.md records.  Runs only on a TPU, like run.py.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--traffic", default=None,
                    help="run the cell's configuration under this traffic "
                         "file instead of its own (a mix not in "
                         "BENCHMARK.json)")
    ap.add_argument("--out", default=None,
                    help="also append each seed's line to this file")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import compare, harness

    overrides = {"also_control": "bfloat16"}
    if args.traffic:
        bench = harness.load_benchmark()
        cell = dict(next(w for w in bench["workloads"]
                         if w["name"] == args.workload),
                    traffic=args.traffic)
        bench["workloads"].append(dict(cell, name=args.workload + "@"
                                       + args.traffic))
        overrides["benchmark"] = bench
        args.workload = args.workload + "@" + args.traffic
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result, extra = harness.run_cell(
            args.workload, seed, args.seconds, False, time.time(),
            overrides=overrides)
        row = {"seed": seed, "correct": result["correct"],
               "program": extra["readings"]["values"],
               "control": extra["control"],
               "compared": extra["readings"]["compared"],
               "worst": extra["readings"]["worst"],
               "e2e": extra["e2e"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(row) + "\n")
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min(r["control"][k] for r in rows)}
               for k in compare.NUMBERS}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
