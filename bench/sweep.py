#!/usr/bin/env python3
"""Knee sweep: one deployment, one open-loop traffic mix, a ladder of rates,
in one process (the set-up is paid once).

    python3 bench/sweep.py --config tpch-lineitem-sf1 --traffic tpch-mix \
        --rates 100,200,400,800 --seconds 8

For each rate it prints the completions per second inside the window, the
latency p50 / p95 from the due time, the generator's lateness, and the
growth of latency from the first fifth of the window to the last (a backlog
that grows shows there), and the host stalls a watchdog saw: while one
lasts, a child process (`stallwatch.py`) reads every thread's state from
/proc, and the first snapshot of each window is printed.  The same rate given several times measures several
windows in one process.  The knee is the highest rate at which completions
keep up with arrivals and latency does not grow; BENCHMARK.json's cells use
fixed fractions of it, written into their traffic files as numbers.  Runs
only on a TPU, like run.py.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro import obs
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    obs.disable()
    import numpy as np

    from bench import drive, harness
    from bench import traffic as traffic_mod

    cfg = harness.load_json(ROOT, "bench", "configs", f"{args.config}.json")
    traffic = traffic_mod.load(args.traffic)
    rng = np.random.default_rng(args.seed)
    gen = harness.load_module("datagen", cfg["data"]["generator"])
    data = gen.generate(rng, cfg["data"]["rows"], cfg["data"].get("params"))
    stats = {c: (float(v.min()), float(v.max())) for c, v in data.items()}
    store = harness.build_store(cfg, data)
    engine = store.engine(backend=cfg["engine"]["backend"],
                          selector=cfg["engine"]["selector"])
    engine.kde_backend = cfg["engine"]["kde_backend"]
    harness.warm(engine, traffic["block"], rng, stats, harness.WARM_SIZES)
    print(f"[sweep] set-up {time.time() - T_START:.1f} s", flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        session = engine.session(
            watermark=int(traffic["session"]["watermark"]),
            max_delay=float(traffic["session"]["max_delay_ms"]) / 1e3)
        t = dict(traffic, rate_qps=rate)
        offsets, specs = traffic_mod.open_schedule(t, args.seconds, rng, stats)
        queries = [traffic_mod.to_query(s) for s in specs]
        st0 = session.stats()
        t0 = time.perf_counter() + 0.02
        watch = drive.StallWatch(t0)
        rec = drive.open_loop(session, queries, offsets, t0,
                              int(traffic.get("submitters", 4)), False)
        drive.wait_all([rec], t0 + args.seconds + 30.0)
        late, snaps = watch.stop()
        st1 = session.stats()
        session.close()
        lat = (rec.done - rec.due) * 1e3
        n = lat.size
        inside = np.count_nonzero(rec.done <= t0 + args.seconds)
        fifth = max(1, n // 5)
        row = {"rate": rate, "sent": n,
               "completed_per_s": inside / args.seconds,
               "p50_ms": float(np.nanpercentile(lat, 50)),
               "p95_ms": float(np.nanpercentile(lat, 95)),
               "first_fifth_p50_ms": float(np.nanpercentile(lat[:fifth], 50)),
               "last_fifth_p50_ms": float(np.nanpercentile(lat[-fifth:], 50)),
               "lateness_p99_ms": float(np.percentile(
                   (rec.sent - rec.due) * 1e3, 99)),
               "mean_batch": (st1["executed"] - st0["executed"])
               / max(1, st1["flushes"] - st0["flushes"]),
               "errors": sum(e is not None for e in rec.error),
               "stalls": sorted(late, key=lambda w: -w[1])[:3],
               "stall_snapshots": len(snaps),
               "stall_threads": (drive.stall_threads(
                   snaps[0], watch.watchdog.names) if snaps else [])}
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
