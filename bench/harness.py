"""One run of one cell: set-up, the measured window, the reference check.

`run_cell` is everything `run.py` does after it has found the chip, so a
test can drive a whole run on the CPU at a small size (`overrides`) and with
the timed path broken underneath.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import compare, drive, traffic as traffic_mod
from bench import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# batch shapes the engine pads a flush to (powers of two from 8, then
# multiples of 64): every one a cell's buckets can reach is compiled in
# set-up, 128 for buckets that a version bump or a race merges past 64
WARM_SIZES = (8, 16, 32, 64, 128)
KDE_SAMPLE = 384        # KDE answers compared per run
EXACT_SAMPLE = 128      # exact answers compared per run
QUALITY_SAMPLE = 32     # answers compared with the exact aggregate
VERSION_SAMPLE = 6      # synopsis versions compared in a refresh cell
DRAIN_S = 60.0          # wait for answers this long past the window


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def load_module(kind: str, name: str, root: str = HERE):
    """`<root>/<kind>/<name>.py` by file name (names may hold '.' and '-');
    `root` is the benchmark's directory."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path} is "
                                f"missing")
    mod_name = "".join(ch if ch.isalnum() else "_"
                       for ch in os.path.relpath(path[:-3], ROOT))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def joint_keys(store_cfg: dict) -> List[tuple]:
    keys = [tuple(j) for j in store_cfg["joints_before_ingest"]]
    keys += [tuple(j) for j in store_cfg["joints_after_ingest"]]
    keys += [tuple(t) for t in store_cfg["tiered"] if len(t) > 1]
    return keys


def spec_key(spec: dict, joints: List[tuple]):
    """The synopsis a spec is answered on: its column, or the tracked joint
    over exactly its columns (predicates, target, group)."""
    cols = []
    for p in spec["preds"]:
        for c in (p[1] if p[0] == "box" else [p[1]]):
            if c not in cols:
                cols.append(c)
    for c in (spec["target"], spec["group_by"]):
        if c is not None and c not in cols:
            cols.append(c)
    if len(cols) == 1:
        return cols[0]
    match = [j for j in joints if set(j) == set(cols)]
    if not match:
        raise KeyError(f"no tracked joint over {cols}")
    return match[0]


def build_store(cfg: dict, data: Dict[str, np.ndarray]):
    from repro.data import TelemetryStore

    st = cfg["store"]
    store = TelemetryStore(capacity=int(st["capacity"]), seed=int(st["seed"]))
    for cols in st["tiered"]:
        store.track_tiered(cols[0] if len(cols) == 1 else tuple(cols),
                           n_tiers=int(st["n_tiers"]))
    for col in st["categorical"]:
        store.track_categorical(col)
    for cols in st["joints_before_ingest"]:
        store.track_joint(tuple(cols))
    store.add_batch(data)
    for cols in st["joints_after_ingest"]:
        store.track_joint(tuple(cols))
    return store


def warm(engine, templates: List[dict], rng, stats, sizes) -> int:
    """Fit every synopsis the traffic reads and compile every padded batch
    shape of every template, through the engine's own entry point."""
    calls = 0
    for t in templates:
        for size in sizes:
            specs = [traffic_mod.make_spec(t, rng, stats) for _ in range(size)]
            engine.execute([traffic_mod.to_query(s) for s in specs])
            calls += 1
    return calls


class LayerContext:
    """What per-layer metric readers see.  Layer files, cost entries and
    cost modules are read from `root`, the benchmark's directory."""

    root = HERE

    def __init__(self, trace, answered, queries_done, refreshes, admission,
                 n, peak):
        self.trace = trace
        self.answered = answered
        self.queries_done = queries_done
        self.refreshes = refreshes
        self.admission = admission
        self.n = n
        self.peak = peak

    def layer(self, name: str) -> dict:
        return load_json(self.root, "layers", f"{name}.json")

    def costs(self, layer: str) -> List[dict]:
        """The layer's cost entries: the `costs` of `layers/<layer>.json`,
        then one entry per file of `layers/<layer>.costs/`, in name order.
        An entry names a program, a `cost` module and, for queries, the
        answer `path` it counts."""
        entries = list(self.layer(layer).get("costs", ()))
        more = os.path.join(self.root, "layers", f"{layer}.costs")
        if os.path.isdir(more):
            entries += [load_json(more, f) for f in sorted(os.listdir(more))
                        if f.endswith(".json")]
        return entries

    def cost(self, name: str):
        return load_module("cost", name, self.root)


_SLOW_EVENTS: List[tuple] = []


def watch_jax_events() -> List[tuple]:
    """(time, event, seconds) of every JAX duration event of 50 ms or more
    (compilation, tracing, cache reads) in this process from the first call
    on; the list is shared by the runs of one process."""
    import jax

    if not _SLOW_EVENTS:
        def on_event(event: str, duration: float, **_kw) -> None:
            if duration >= 0.05:
                _SLOW_EVENTS.append((time.perf_counter(), event,
                                     round(duration, 3)))
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _SLOW_EVENTS.append((0.0, "listening", 0.0))
    return _SLOW_EVENTS


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The q-quantile of `values` by nearest rank; a query that failed is
    +inf and counts as missing every limit."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q * v.size) - 1)])


def _base_path(path: str) -> str:
    return path.replace(":pallas", "").replace(":jnp", "")


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool,
             t_start: float, overrides: Optional[dict] = None,
             control: Optional[str] = None, log=None):
    """Run one cell once; returns (the result line's dict, with `checks`
    last, and a dict of what else the run measured)."""
    import jax

    from repro import obs

    overrides = overrides or {}
    log = log or (lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                    flush=True))
    obs.disable()          # its fences would change what is timed
    bench = overrides.get("benchmark") or load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = traffic_mod.load(cell["traffic"])
    for k, v in overrides.get("config", {}).items():
        cfg[k].update(v) if isinstance(v, dict) else cfg.__setitem__(k, v)
    traffic.update(overrides.get("traffic", {}))
    limits = load_json(HERE, "limits", f"{cfg['name']}.json")
    # the check follows the configuration's selector: a selector with no
    # estimator module stops the run here, before any data is made
    est = load_module("estimators", cfg["engine"]["selector"])
    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if cell_name in m.get("workloads", [cell_name])]
    layer_names = [m["name"] for m in bench["per_layer"]
                   if cell_name in m.get("workloads", [cell_name])]

    ss = np.random.SeedSequence(int(seed))
    data_rng, traffic_rng, refresh_rng, sample_rng, warm_rng = (
        np.random.default_rng(s) for s in ss.spawn(5))

    phases = {"start": time.time() - t_start}
    t_phase = time.perf_counter()
    gen = load_module("datagen", cfg["data"]["generator"])
    data = gen.generate(data_rng, cfg["data"]["rows"],
                        cfg["data"].get("params"))
    stats = {c: (float(v.min()), float(v.max())) for c, v in data.items()}
    phases["data"] = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    store = build_store(cfg, data)
    phases["ingest"] = time.perf_counter() - t_phase
    eng_cfg = cfg["engine"]
    engine = store.engine(backend=eng_cfg["backend"],
                          selector=eng_cfg["selector"])
    engine.kde_backend = eng_cfg["kde_backend"]
    sess_cfg = traffic["session"]
    session = engine.session(watermark=int(sess_cfg["watermark"]),
                             max_delay=float(sess_cfg["max_delay_ms"]) / 1e3)
    joints = joint_keys(cfg["store"])
    t_phase = time.perf_counter()

    if traffic["loop"] != "open":
        raise ValueError(f"traffic {cell['traffic']!r}: loop "
                         f"{traffic['loop']!r} is not driven (only 'open')")
    submitters = int(traffic.get("submitters", 4))
    offsets, specs = traffic_mod.open_schedule(traffic, seconds, traffic_rng,
                                               stats)
    queries = [traffic_mod.to_query(s) for s in specs]
    templates = list(traffic["block"])
    refresh = traffic.get("refresh")
    batches: List[dict] = []
    probes, probe_specs, probe_key = [], [], None
    if refresh:
        n_ref = int(seconds / float(refresh["every_s"]))
        batches = [gen.generate(refresh_rng, refresh["rows"],
                                cfg["data"].get("params"))
                   for _ in range(n_ref)]
        probe_specs = [traffic_mod.make_spec(refresh["probe"], refresh_rng,
                                             stats) for _ in range(n_ref)]
        probes = [traffic_mod.to_query(s) for s in probe_specs]
        probe_key = spec_key(probe_specs[0], joints)
        templates.append(refresh["probe"])

    phases["traffic"] = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    warm(engine, templates, warm_rng, stats,
         overrides.get("warm_sizes", WARM_SIZES))
    phases["fit_and_warm"] = time.perf_counter() - t_phase
    log("set-up phases (s): " + ", ".join(f"{k} {v:.2f}"
                                          for k, v in phases.items()))

    # what set-up built stays alive for the whole run: keep it out of the
    # collector's scans, so that a window's pauses are the program's own
    gc.collect()
    gc.freeze()
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = drive.annotation(traced)
    slow_events = watch_jax_events()
    jax.config.update("jax_log_compiles", True)
    st0 = session.stats()
    t0 = time.perf_counter() + 0.02
    setup_s = time.time() + (t0 - time.perf_counter()) - t_start
    t_end = t0 + seconds
    records = []
    refresher = None
    gate = drive.Gate() if refresh and refresh.get("quiesce") else None
    rec = drive.Record(len(queries))
    watchdog = drive.Watchdog(t0)
    steal0 = drive.cpu_steal_s()
    with span("bench.window"):
        if refresh:
            refresher = drive.Refresher(
                store, session, batches, probes, float(refresh["every_s"]),
                traced, gate=gate,
                drained=lambda: drive.outstanding([rec, refresher.rec]) == 0)
            refresher.start(t0)
        drive.open_loop(session, queries, offsets, t0, submitters, traced,
                        rec=rec, gate=gate)
        wait = t_end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
    late_wakeups = watchdog.stop()
    steal1 = drive.cpu_steal_s()
    records.append(rec)
    if refresher is not None:
        refresher.join()
        records.append(refresher.rec)
    drive.wait_all(records, t_end + DRAIN_S)
    st1 = session.stats()
    jax.config.update("jax_log_compiles", False)
    gc.unfreeze()
    trace_summary = None
    if traced:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    if traced:
        try:
            trace_summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # --- what the window produced -------------------------------------------
    sent = ~np.isnan(rec.sent)
    errors = [i for i in range(len(rec.error)) if rec.error[i] is not None]
    answered = sent & ~np.isnan(rec.done) & np.asarray(
        [e is None for e in rec.error])
    attempted = len(queries)
    lat_ms = np.where(answered, (rec.done - rec.due) * 1e3, np.inf)
    unanswered = attempted - int(np.count_nonzero(answered))
    in_window = answered & (rec.done >= t0) & (rec.done <= t_end)

    metrics: Dict[str, dict] = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    e2e = {"setup_s": setup_s, "query_p95_ms": nearest_rank(lat_ms, 0.95)}
    stale = 0
    if refresher is not None:
        pr = refresher.rec
        ok = ~np.isnan(pr.done) & np.asarray([e is None for e in pr.error])
        unanswered += int(np.count_nonzero(~ok))
        fresh = (pr.done - refresher.inserted)[ok] * 1e3
        e2e["fresh_answer_ms"] = float(np.mean(fresh)) if fresh.size \
            else math.inf
        # the first ingest makes every version 1 and each insert adds one:
        # probe k follows insert k, so it must be answered on 2 + k or later
        for k in range(len(pr.result)):
            r = pr.result[k]
            if r is not None and r.synopsis_version < 2 + k:
                stale += 1
    if not traced:
        for name in e2e_names:
            metrics[name] = {"value": e2e[name], "unit": units[name]}

    # per-layer context (answers completed inside the window)
    n = int(cfg["store"]["capacity"])
    parts = []
    done_queries = 0
    for i in np.flatnonzero(in_window):
        done_queries += 1
        res = rec.result[i]
        for r in (res if isinstance(res, list) else [res]):
            base = _base_path(r.path)
            if base.startswith("exact"):
                continue
            key = spec_key(specs[i], joints)
            d = len(key) if isinstance(key, tuple) else 1
            groups = len(res) if isinstance(res, list) else 1
            parts.append((base, d, groups))
    admission = {"flushes": st1["flushes"] - st0["flushes"],
                 "rows": st1["executed"] - st0["executed"]}
    refreshes = 0
    if refresher is not None:
        refreshes = int(np.count_nonzero(refresher.inserted <= t_end))
    if traced:
        peaks = load_json(HERE, "peaks.json")
        if dev.device_kind not in peaks:
            raise RuntimeError(f"no peaks for device kind "
                               f"{dev.device_kind!r} in bench/peaks.json")
        ctx = LayerContext(trace_summary, parts, done_queries, refreshes,
                           admission, n, peaks[dev.device_kind])
        for name in layer_names:
            value = load_module("metrics", name).read(ctx)
            if value is not None:
                unit = next(m["unit"] for m in bench["per_layer"]
                            if m["name"] == name)
                metrics[name] = {"value": value, "unit": unit}

    lateness = (rec.sent - rec.due)[sent] * 1e3
    in_win = [(t - t0, name, dur) for t, name, dur in slow_events
              if t0 <= t <= t_end]
    if in_win:
        log(f"JAX events of 50 ms or more inside the window (offset s, "
            f"event, s): {in_win[:12]}")
    if steal0 is not None and steal1 is not None:
        log(f"CPU time stolen from this machine by its host during the "
            f"window: {steal1 - steal0:.2f} s (all cores)")
    if late_wakeups:
        top = sorted(late_wakeups, key=lambda w: -w[1])[:3]
        log(f"host stalls inside the window: {len(late_wakeups)} wake-ups of "
            f"a 50 ms watchdog 100 ms or more late; longest (offset s, late s, "
            f"process CPU s meanwhile, involuntary switches): {top}")
    worst = int(np.nanargmax(rec.sent - rec.due))
    log(f"largest lateness {lateness.max():.1f} ms at {rec.due[worst] - t0:.3f} "
        f"s into the window")
    log(f"window {seconds:g} s: {attempted} queries due/sent, "
        f"{int(np.count_nonzero(in_window))} answered inside the window, "
        f"{unanswered} unanswered, {len(errors)} errors; generator lateness "
        f"p50 {np.percentile(lateness, 50):.3f} ms p99 "
        f"{np.percentile(lateness, 99):.3f} ms; admission "
        f"{admission['flushes']} flushes, {admission['rows']} queries")
    if errors:
        log(f"first error: {rec.error[errors[0]]!r}")

    # --- hand the answers to the check; free the program's state -------------
    sampled = _sample_answers(rec, specs, joints, sample_rng,
                              refresher, probe_specs, probe_key)
    h_prog = {}
    final_version = 1 + len(batches)
    for key in sorted({it[2] for it in sampled["kde"]}, key=str):
        res_obj = (store.joints[key] if isinstance(key, tuple)
                   else store.columns[key])
        syn = store.cache.peek(key, eng_cfg["selector"], res_obj.version)
        if syn is not None and res_obj.version == final_version:
            h_prog[(key, res_obj.version)] = est.program_bandwidth(syn)
    session.close()
    del session, engine, store, queries, probes
    gc.collect()

    work = {"store": cfg["store"], "data": data, "batches": batches,
            "kde": sampled["kde"], "exact": sampled["exact"],
            "h_prog": h_prog, "unanswered": unanswered, "stale": stale}
    t_ref = time.perf_counter()
    checked = compare.readings(work, est, control=control)
    also = overrides.get("also_control")
    control_readings = (compare.readings(work, est, control=also)["values"]
                        if also else None)
    qual = compare.quality(sampled["kde"][:QUALITY_SAMPLE], data, batches)
    log(f"reference check {time.perf_counter() - t_ref:.2f} s over "
        f"{checked['compared']}; answer quality vs the exact aggregate over "
        f"every row: {qual}")
    values = checked["values"]
    correct = compare.verdict(values, limits)
    if not correct:
        log(f"worst answers: {checked['worst']}")
    checks = {k: {"value": values[k], "limit": limits[k]}
              for k in compare.NUMBERS}
    result = {
        "correct": bool(correct),
        "attempted": int(attempted + len(batches)),
        "failed": int(unanswered),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak_bytes},
    }
    if traced:
        result["device"]["busy_s"] = trace_summary["busy_s"]
        result["device"]["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["checks"] = checks
    return result, {"e2e": e2e, "trace": trace_summary, "quality": qual,
                    "readings": checked, "lateness_ms": lateness,
                    "control": control_readings}


def _sample_answers(rec, specs, joints, rng, refresher, probe_specs,
                    probe_key) -> dict:
    """Answers the check compares, drawn from the seed: KDE answer parts
    (at most VERSION_SAMPLE synopsis versions, the last always among them)
    and exact answers, plus every probe at a sampled version."""
    kde, exact = [], []
    for i in range(len(rec.result)):
        res = rec.result[i]
        if res is None:
            continue
        spec = specs[i]
        key = spec_key(spec, joints)
        for r in (res if isinstance(res, list) else [res]):
            item = (spec, r, key, int(r.synopsis_version))
            (exact if r.path.startswith("exact") else kde).append(item)
    probes = []
    if refresher is not None:
        for k, r in enumerate(refresher.rec.result):
            if r is not None:
                probes.append((probe_specs[k], r, probe_key,
                               int(r.synopsis_version)))
    versions = sorted({it[3] for it in kde + probes})
    if len(versions) > VERSION_SAMPLE:
        keep = set(rng.choice(versions[:-1], VERSION_SAMPLE - 1,
                              replace=False).tolist())
        keep.add(versions[-1])
        kde = [it for it in kde if it[3] in keep]
        probes = [it for it in probes if it[3] in keep]
    pick = rng.permutation(len(kde))[:KDE_SAMPLE]
    kde = [kde[j] for j in sorted(pick)] + probes
    pick = rng.permutation(len(exact))[:EXACT_SAMPLE]
    exact = [exact[j] for j in sorted(pick)]
    return {"kde": kde, "exact": exact}
