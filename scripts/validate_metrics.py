#!/usr/bin/env python
"""Schema-check observability artifacts (CI gate).

    python scripts/validate_metrics.py /tmp/aqp-metrics.json
    python scripts/validate_metrics.py --bench BENCH_aqp.json
    python scripts/validate_metrics.py --tuning /tmp/tiles.json

Default mode validates a `repro.launch.serve --metrics-out` snapshot
(`obs.export_json` format): the required instruments must be present with
sane values — queue depth gauge, per-path latency histograms, synopsis
cache hit/miss counters, and flush-reason counters.  `--bench` validates a
`benchmarks.run --json` report instead; `--tuning` a persisted tile cache
(`kernels/autotune.py` REPRO_TUNING_CACHE format), enforcing on top of the
schema that every swept winner is no slower than the env/default tiles it
was measured against.  Exits non-zero with one line per violation.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List

HIST_KEYS = {"labels", "count", "sum", "mean", "min", "max",
             "p50", "p95", "p99"}


def _entries(doc: dict, kind: str, name: str, errs: List[str]) -> list:
    entries = doc.get(kind, {}).get(name)
    if not entries:
        errs.append(f"missing {kind[:-1]} {name!r}")
        return []
    for e in entries:
        if "labels" not in e or not isinstance(e["labels"], dict):
            errs.append(f"{name}: entry without labels dict: {e}")
    return entries


def validate_metrics(doc: dict) -> List[str]:
    errs: List[str] = []
    for key in ("ts", "counters", "gauges", "histograms"):
        if key not in doc:
            errs.append(f"missing top-level key {key!r}")
    if errs:
        return errs

    # queue depth gauge, one per session
    for e in _entries(doc, "gauges", "aqp.admission.depth", errs):
        if "session" not in e["labels"]:
            errs.append(f"aqp.admission.depth entry missing session label: "
                        f"{e['labels']}")
        if e.get("value", -1) < 0:
            errs.append(f"aqp.admission.depth negative: {e}")

    # per-path latency histograms with full summaries
    paths = set()
    for e in _entries(doc, "histograms", "aqp.query.latency_us", errs):
        missing = HIST_KEYS - set(e)
        if missing:
            errs.append(f"aqp.query.latency_us entry missing {sorted(missing)}")
            continue
        paths.add(e["labels"].get("path"))
        if e["count"] > 0 and not (e["min"] <= e["p50"] <= e["p95"]
                                   <= e["p99"] <= e["max"]):
            errs.append(f"aqp.query.latency_us percentiles out of order for "
                        f"path={e['labels'].get('path')}")
    if not paths - {None}:
        errs.append("aqp.query.latency_us has no path-labelled entries")

    # cache hit rates need both counters present (zero values are fine)
    _entries(doc, "counters", "aqp.cache.hits", errs)
    _entries(doc, "counters", "aqp.cache.misses", errs)

    # flush reasons, every label from the admission vocabulary ("fit" is the
    # offloaded-synopsis-fit re-flush)
    known = {"watermark", "deadline", "manual", "close", "fit"}
    for e in _entries(doc, "counters", "aqp.admission.flush_reason", errs):
        reason = e["labels"].get("reason")
        if reason not in known:
            errs.append(f"unknown flush reason {reason!r}")

    # synopsis-backend instruments are conditional: they only exist once a
    # full-H query ran through the pluggable backend layer, but when present
    # they must be backend-labelled and well-formed
    for name in ("aqp.synopsis.hits", "aqp.synopsis.fallback"):
        for e in doc.get("counters", {}).get(name, []):
            if e.get("labels", {}).get("backend") not in ("exact", "rff"):
                errs.append(f"{name} entry missing/unknown backend label: "
                            f"{e.get('labels')}")
    for name in ("aqp.synopsis.fit_us", "aqp.synopsis.eval_us"):
        for e in doc.get("histograms", {}).get(name, []):
            missing = HIST_KEYS - set(e)
            if missing:
                errs.append(f"{name} entry missing {sorted(missing)}")
            elif e.get("labels", {}).get("backend") != "rff":
                errs.append(f"{name} entry missing backend=rff label: "
                            f"{e.get('labels')}")
    return errs


def validate_bench(doc: dict) -> List[str]:
    errs: List[str] = []
    for key in ("git_sha", "ts", "config", "results"):
        if key not in doc:
            errs.append(f"missing top-level key {key!r}")
    if errs:
        return errs
    if not doc["results"]:
        errs.append("empty results list")
    timed = set()
    for r in doc["results"]:
        for key in ("name", "us_per_call"):
            if key not in r:
                errs.append(f"result missing {key!r}: {r}")
        # us_per_call == 0 marks a non-timing row (parity checks, skipped
        # suites); negative is always a bug
        if r.get("us_per_call", -1) < 0:
            errs.append(f"negative us_per_call: {r.get('name')}")
        if r.get("us_per_call", 0) > 0:
            timed.add(r.get("name", ""))
    if not any(n.startswith("aqp_") for n in timed):
        errs.append("no timed aqp_* benchmark results present")
    return errs


def validate_tuning(doc: dict) -> List[str]:
    errs: List[str] = []
    if doc.get("version") != 1:
        errs.append(f"unsupported tile-cache version {doc.get('version')!r}")
        return errs
    if "ts" not in doc:
        errs.append("missing top-level key 'ts'")
    entries = doc.get("entries")
    if not isinstance(entries, list) or not entries:
        errs.append("empty or missing entries list")
        return errs
    for e in entries:
        name = e.get("kernel", "<unnamed>")
        for key in ("kernel", "shape", "key", "tiles", "us",
                    "default_tiles", "default_us", "repeats", "swept"):
            if key not in e:
                errs.append(f"{name}: entry missing {key!r}")
        for field in ("shape", "tiles", "default_tiles"):
            v = e.get(field)
            if isinstance(v, dict):
                bad = {k: x for k, x in v.items()
                       if not isinstance(x, int) or x <= 0}
                if bad:
                    errs.append(f"{name}: non-positive {field} values {bad}")
        if e.get("us", -1) <= 0:
            errs.append(f"{name}: non-positive winning time {e.get('us')}")
        # the invariant the sweep guarantees by construction (the default
        # config is always candidate #0): tuned tiles never regress
        if "us" in e and "default_us" in e and e["us"] > e["default_us"]:
            errs.append(f"{name}: tuned tiles SLOWER than defaults "
                        f"({e['us']:.1f}us > {e['default_us']:.1f}us)")
        swept = e.get("swept")
        if isinstance(swept, list):
            if not any(s.get("tiles") == e.get("tiles") for s in swept):
                errs.append(f"{name}: winning tiles absent from swept list")
            if swept and swept[0].get("tiles") != e.get("default_tiles"):
                errs.append(f"{name}: candidate #0 is not the default config")
    return errs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="JSON artifact to validate")
    ap.add_argument("--bench", action="store_true",
                    help="validate a benchmarks.run --json report instead "
                         "of a metrics snapshot")
    ap.add_argument("--tuning", action="store_true",
                    help="validate a kernels/autotune.py tile cache instead "
                         "of a metrics snapshot")
    args = ap.parse_args()
    with open(args.path, encoding="utf-8") as f:
        doc = json.load(f)
    if args.bench and args.tuning:
        print("FAIL: --bench and --tuning are mutually exclusive",
              file=sys.stderr)
        return 1
    if args.bench:
        errs, kind = validate_bench(doc), "bench report"
    elif args.tuning:
        errs, kind = validate_tuning(doc), "tile cache"
    else:
        errs, kind = validate_metrics(doc), "metrics snapshot"
    for e in errs:
        print(f"FAIL {args.path}: {e}", file=sys.stderr)
    if not errs:
        print(f"OK {args.path}: valid {kind}")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
