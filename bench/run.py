#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of `workloads` in BENCHMARK.json) names a configuration
and a traffic mix.  The run builds the deployment from the seed, warms every
shape its traffic uses (that is `setup_s`), drives the traffic for
`--seconds` seconds, then checks a sample of the answers against the plain
reference.  With `--trace 1` the window runs under the JAX profiler and the
result carries the per-layer metrics instead of the end-to-end ones.

The last lines on standard error give each compared number beside its limit;
the last line on standard output is the result, one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no result
and exits non-zero.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None,
                    help="put the reference computed in this precision in the "
                         "program's place (it must come out not correct); "
                         "used to set the limits, never by a measured run")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    for path in (ROOT, src):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    try:
        result, _extra = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            T_START, control=args.control)
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    for name, chk in result["checks"].items():
        print(f"check {name} = {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
