#!/usr/bin/env python3
"""Knee sweep of a cell whose traffic inserts rows: the cell's configuration
and traffic mix, its refresh running as the cell runs it (an insert every
`every_s` at a quiet point, then the probe), at a ladder of query rates, in
one process (the set-up is paid once).

    python3 bench/sweep_refresh.py --workload telemetry.fit-lscv \
        --rates 560,720,880,1040,1200 --seconds 30

`bench/sweep.py` drives no refresh, so it cannot find the knee of such a
cell: there the refits after each insert hold the device, and the queries
due meanwhile queue behind them.  For each rate this prints completions per
second inside the window, latency p50 / p95 from the due time, the probes'
freshness, and the backlog just before each insert: the queries due and not
yet answered at the insert's due time.  Each of those readings sits at the
same phase of the refresh cycle, so a backlog the system does not clear
between inserts shows as a count that grows from insert to insert.

A rate is sustained when (1) at least 98% of the window's queries complete
inside it, and (2) the backlog before the window's last insert is at most
twice that before its first, or twice the queries due in 0.1 s, whichever
is larger.  The knee is the highest rate sustained, with every lower rate of
the ladder sustained too; the cell's traffic file gives its rate as a
number, a fixed fraction of the knee.  Runs only on a TPU, like run.py.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEEP_UP = 0.98          # share of a window's queries completed inside it
BACKLOG_GROWTH = 2.0    # last insert's backlog over the first's
BACKLOG_FLOOR_S = 0.1   # backlog floor, in seconds of arrivals


def backlog(rec, at) -> list:
    """Queries due by each time of `at` and not answered by it."""
    import numpy as np

    done = np.where(np.isnan(rec.done), np.inf, rec.done)
    return [int(np.count_nonzero((rec.due <= t) & (done > t))) for t in at]


def sustained(row: dict) -> bool:
    rate, b = row["rate"], row["backlog_before_insert"]
    floor = rate * BACKLOG_FLOOR_S
    return (row["completed"] >= KEEP_UP * row["sent"]
            and b[-1] <= BACKLOG_GROWTH * max(b[0], floor))


def knee(rows: list):
    """The highest rate sustained with every lower rate sustained too."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if not row["sustained"]:
            break
        best = row["rate"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json whose traffic refreshes")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_refresh: no TPU", file=sys.stderr)
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

    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(ROOT, cfg_entry["file"])
    traffic = traffic_mod.load(cell["traffic"])
    refresh = traffic.get("refresh")
    if not refresh:
        print(f"sweep_refresh: traffic {cell['traffic']!r} has no refresh; "
              f"use bench/sweep.py", file=sys.stderr)
        return 2
    every = float(refresh["every_s"])
    rng = np.random.default_rng(args.seed)
    gen = harness.load_module("datagen", cfg["data"]["generator"])
    params = cfg["data"].get("params")
    data = gen.generate(rng, cfg["data"]["rows"], params)
    stats = {c: (float(v.min()), float(v.max())) for c, v in data.items()}
    store = harness.build_store(cfg, data)
    engine = store.engine(backend=cfg["engine"]["backend"],
                          selector=cfg["engine"]["selector"])
    engine.kde_backend = cfg["engine"]["kde_backend"]
    harness.warm(engine, list(traffic["block"]) + [refresh["probe"]], rng,
                 stats, harness.WARM_SIZES)
    print(f"[sweep] set-up {time.time() - T_START:.1f} s", flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        session = engine.session(
            watermark=int(traffic["session"]["watermark"]),
            max_delay=float(traffic["session"]["max_delay_ms"]) / 1e3)
        t = dict(traffic, rate_qps=rate)
        offsets, specs = traffic_mod.open_schedule(t, args.seconds, rng, stats)
        queries = [traffic_mod.to_query(s) for s in specs]
        n_ref = int(args.seconds / every)
        batches = [gen.generate(rng, refresh["rows"], params)
                   for _ in range(n_ref)]
        probes = [traffic_mod.to_query(traffic_mod.make_spec(
            refresh["probe"], rng, stats)) for _ in range(n_ref)]
        gate = drive.Gate() if refresh.get("quiesce") else None
        rec = drive.Record(len(queries))
        t0 = time.perf_counter() + 0.02
        refresher = drive.Refresher(
            store, session, batches, probes, every, False, gate=gate,
            drained=lambda: drive.outstanding([rec, refresher.rec]) == 0)
        refresher.start(t0)
        drive.open_loop(session, queries, offsets, t0,
                        int(traffic.get("submitters", 4)), False, rec=rec,
                        gate=gate)
        refresher.join()
        drive.wait_all([rec, refresher.rec], t0 + args.seconds + 60.0)
        session.close()
        lat = (rec.done - rec.due) * 1e3
        fresh = (refresher.rec.done - refresher.inserted) * 1e3
        inside = np.count_nonzero(rec.done <= t0 + args.seconds)
        row = {"rate": rate, "sent": int(lat.size), "completed": int(inside),
               "completed_per_s": inside / args.seconds,
               "p50_ms": float(np.nanpercentile(lat, 50)),
               "p95_ms": float(np.nanpercentile(lat, 95)),
               "fresh_ms": [round(float(f), 1) for f in fresh],
               "backlog_before_insert": backlog(
                   rec, [t0 + (k + 0.5) * every for k in range(n_ref)]),
               "errors": sum(e is not None for e in rec.error)}
        row["sustained"] = sustained(row)
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "knee": knee(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
