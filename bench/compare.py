"""The comparison that decides `correct`: the program's answers against the
plain reference (`reference.py`), each number beside its limit.

Numbers (limits in `limits/<config>.json`):

  est_gap     largest gap between a sampled KDE answer and the reference's,
              in units of the relation: |d COUNT| / rows, |d SUM| / (rows M),
              |d AVG| C / (rows M), with M = max |x_target| + h_target over
              the sample and C the reference count
  ci_gap      the same for the 95% interval's half-width
  h_gap       largest gap of a bandwidth, as the estimator module measures
              it (plugin: relative, per axis), over every synopsis fitted at
              its column's final version
  exact_gap   largest |answer - exact count| of a sampled Eq answer
  unanswered  queries due in the window (and probes) that never got an
              answer, or got an error
  stale       probes answered on a synopsis version older than the refresh
              they followed

The bandwidths and KDE answers come from the estimator module of the
configuration's `engine.selector` (`estimators/<selector>.py`), handed in as
`est`.  In control mode the reference computed in bfloat16 takes the
program's place (estimates, intervals, bandwidths and exact counts): it must
come out not correct.

Besides the numbers, `readings` names the worst KDE answer and the worst
exact answer, each with its gap against the reference at the synopsis
versions just before and after the one the answer carries: an answer
computed on another version than its label says reads near 0 there.  For
the worst KDE answer it also scans the buffer states a reader could copy
while an insert writes the reservoir (`torn`): the state and rows-seen
count nearest the program's answer.
"""
from __future__ import annotations

import math
from collections import defaultdict
from typing import Dict, Optional, Sequence

import numpy as np

from bench import reference as ref

NUMBERS = ("est_gap", "ci_gap", "h_gap", "exact_gap", "unanswered", "stale")


def gap(agg: str, diff: float, n_seen: float, m_t: float,
        count: float) -> float:
    if agg == "count":
        return abs(diff) / n_seen
    if agg == "sum":
        return abs(diff) / (n_seen * m_t)
    return abs(diff) * max(count, ref.AVG_MIN_COUNT) / (n_seen * m_t)


def _half(r) -> float:
    return (r.ci_hi - r.ci_lo) / 2.0


def readings(work: dict, est, control: Optional[str] = None) -> dict:
    """Compare a run's sampled answers with the reference of estimator
    module `est`.

    `work` holds: the store's configuration, the first batch and the refresh
    batches, the sampled answers [(spec, result, key, version)] and exact
    answers, the program's bandwidths {(key, version): h array}, and the
    counts of unanswered and stale queries.
    """
    cfg_store = work["store"]
    keys_versions = set()
    for spec, r, key, version in work["kde"]:
        keys_versions.add((key, version))
    for (key, version) in work["h_prog"]:
        keys_versions.add((key, version))
    keys = sorted({k for k, _v in keys_versions}, key=ref.key_name)
    versions = sorted({v for _k, v in keys_versions})
    last = 1 + len(work["batches"])
    near = set()
    if control is None:
        near = {(k, v + dv) for k, v in keys_versions for dv in (-1, 1)
                if 1 <= v + dv <= last}

    # replay the reservoirs to every version needed
    samples: Dict[tuple, tuple] = {}
    if keys:
        res = ref.build_reservoirs(cfg_store, keys, work["data"])
        applied = 1
        for v in sorted({v for _k, v in keys_versions | near}):
            while applied < v:
                ref.add_rows(res, work["batches"][applied - 1])
                applied += 1
            for k in keys:
                if (k, v) in keys_versions or (k, v) in near:
                    samples[(k, v)] = (res[k].sample(), res[k].n_seen,
                                       res[k].writes)

    prec = ref.Precision(control) if control else None
    h_ref: Dict[tuple, object] = {}
    h_ctl: Dict[tuple, object] = {}
    for kv in keys_versions:
        h_ref[kv] = est.bandwidth(samples[kv][0], ref.F64)
        if prec is not None:
            h_ctl[kv] = est.bandwidth(samples[kv][0], prec)

    # bandwidths
    h_gap = 0.0
    for kv, h_p in work["h_prog"].items():
        if prec is not None:
            h_p = h_ctl[kv]
        h_gap = max(h_gap, est.bandwidth_gap(h_p, h_ref[kv]))

    # KDE answers, grouped by synopsis and version
    by_kv = defaultdict(list)
    for item in work["kde"]:
        by_kv[(item[2], item[3])].append(item)
    est_gap = ci_gap = 0.0
    worst = worst_item = None
    for kv, items in by_kv.items():
        x, n_seen, _writes = samples[kv]
        cols = kv[0] if isinstance(kv[0], tuple) else (kv[0],)
        boxes = [ref.box_of(spec, cols, r.group) for spec, r, _k, _v in items]
        aggs = [spec["agg"] for spec, _r, _k, _v in items]
        truth = est.answers(boxes, aggs, x, h_ref[kv], n_seen, ref.F64)
        if prec is not None:
            got = est.answers(boxes, aggs, x, h_ctl[kv], n_seen, prec)
        else:
            got = [(r.estimate, _half(r)) for _s, r, _k, _v in items]
        for (spec, r, _k, _v), t, g in zip(items, truth, got):
            est_r, hw_r, c_r, m_t = t
            e = gap(spec["agg"], g[0] - est_r, n_seen, m_t, c_r)
            if math.isinf(hw_r) and math.isinf(g[1]):
                c = 0.0
            else:
                c = gap(spec["agg"], g[1] - hw_r, n_seen, m_t, c_r)
            if not math.isfinite(e):
                e = math.inf
            if not math.isfinite(c):
                c = math.inf
            if e > est_gap:
                est_gap = e
                worst_item = (spec, r, kv)
                worst = {"spec": spec, "group": r.group, "program": g[0],
                         "reference": est_r, "version": kv[1], "gap": e}
            ci_gap = max(ci_gap, c)

    if worst_item is not None and control is None:
        worst["gap_at"] = _gaps_at_neighbours(worst_item, samples, est)
        worst["torn"] = _torn_scan(worst_item, samples,
                                   h_ref[worst_item[2]], est)

    # exact answers
    def exact_truth(spec, version):
        col, value = spec["preds"][0][1], spec["preds"][0][2]
        return ref.exact_count([work["data"][col]]
                               + [b[col] for b in
                                  work["batches"][:version - 1]], value)

    exact_gap = 0.0
    worst_exact = None
    for spec, r, key, version in work["exact"]:
        truth = exact_truth(spec, version)
        got = r.estimate
        if prec is not None:
            got = float(prec.r(np.float32(truth)))
        if abs(got - truth) > exact_gap:
            exact_gap = abs(got - truth)
            worst_exact = {"spec": spec, "program": got, "reference": truth,
                           "version": version, "gap_at": {
                               f"v{v}": abs(got - exact_truth(spec, v))
                               for v in (version - 1, version + 1)
                               if 1 <= v <= last and control is None}}

    return {
        "values": {"est_gap": est_gap, "ci_gap": ci_gap, "h_gap": h_gap,
                   "exact_gap": exact_gap,
                   "unanswered": float(work["unanswered"]),
                   "stale": float(work["stale"])},
        "compared": {"kde": len(work["kde"]),
                     "exact": len(work["exact"]),
                     "bandwidths": len(work["h_prog"]),
                     "versions": len(versions)},
        "worst": {"estimate": worst, "exact": worst_exact},
    }


def _gaps_at_neighbours(item, samples, est) -> Dict[str, float]:
    """The gap of one KDE answer against the reference at the synopsis
    versions next to the one it carries (where the window has them)."""
    spec, r, (key, version) = item
    cols = key if isinstance(key, tuple) else (key,)
    out = {}
    for v in (version - 1, version + 1):
        if (key, v) not in samples:
            continue
        x, n_seen, _writes = samples[(key, v)]
        e, _hw, count, m_t = est.answers(
            [ref.box_of(spec, cols, r.group)], [spec["agg"]], x,
            est.bandwidth(x, ref.F64), n_seen, ref.F64)[0]
        out[f"v{v}"] = gap(spec["agg"], r.estimate - e, n_seen, m_t, count)
    return out


TORN_MAX_WRITES = 4096


def _torn_scan(item, samples, h, est) -> Optional[dict]:
    """The reference over every buffer state from version v - 1 to v + 1,
    one write of the adds that made v and v + 1 at a time, in the order the
    reservoir makes them, with v's bandwidth and each version's rows-seen
    count: the state nearest the program's answer (`offset` -j: j writes of
    insert v still missing; +j: j writes of insert v + 1 already in), its
    gap, and the gap again with that state's own bandwidth."""
    spec, r, (key, version) = item
    cols = key if isinstance(key, tuple) else (key,)
    box = ref.box_of(spec, cols, r.group)
    x_v, n_v, writes_v = samples[(key, version)]
    offsets, states = [], []
    before = samples.get((key, version - 1))
    if (before is not None and before[0].shape == x_v.shape
            and writes_v[0].size <= TORN_MAX_WRITES):
        buf = before[0].copy()
        for j in range(writes_v[0].size):
            offsets.append(j - writes_v[0].size)
            states.append(buf.copy())
            buf[writes_v[0][j]] = writes_v[1][j]
    offsets.append(0)
    states.append(x_v)
    after = samples.get((key, version + 1))
    if (after is not None and after[0].shape == x_v.shape
            and after[2][0].size <= TORN_MAX_WRITES):
        buf = x_v.copy()
        for j in range(after[2][0].size):
            buf[after[2][0][j]] = after[2][1][j]
            offsets.append(j + 1)
            states.append(buf.copy())
    seen = {f"v{v}": samples[(key, v)][1] for v in
            (version - 1, version, version + 1) if (key, v) in samples}
    _e, _h, count_v, m_v = est.answers([box], [spec["agg"]], x_v, h, n_v,
                                       ref.F64)[0]
    best = None
    for off, x in zip(offsets, states):
        a = est.answers([box], [spec["agg"]], x, h, n_v, ref.F64)[0][0]
        for name, n in seen.items():
            e = a if spec["agg"] == "avg" else a * n / n_v
            g = gap(spec["agg"], r.estimate - e, n_v, m_v, count_v)
            if best is None or g < best[0]:
                best = (g, off, name, x, n)
    g, off, name, x, n = best
    a = est.answers([box], [spec["agg"]], x, est.bandwidth(x, ref.F64), n,
                    ref.F64)[0][0]
    return {"offset": off, "n_seen_of": name, "gap": g,
            "gap_own_h": gap(spec["agg"], r.estimate - a, n_v, m_v,
                             count_v),
            "writes": [int(writes_v[0].size),
                       int(after[2][0].size) if after is not None else 0]}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(values[k] <= limits[k] for k in NUMBERS)


def quality(items: Sequence[tuple], data: dict, batches: list) -> dict:
    """Relative error of sampled estimates against the exact aggregate over
    every row ingested up to their version, and 95% interval coverage."""
    rel, covered = [], 0
    by_version = {1: data}
    for spec, r, _key, version in items:
        rows = by_version.get(version)
        if rows is None:
            rows = by_version[version] = {
                c: np.concatenate([data[c]] + [b[c] for b in
                                               batches[:version - 1]])
                for c in data}
        truth = ref.exact_aggregate(spec, rows, r.group)
        covered += int(r.ci_lo <= truth <= r.ci_hi)
        if truth != 0.0:
            rel.append(abs(r.estimate - truth) / abs(truth))
    if not items:
        return {"n": 0}
    return {"n": len(items),
            "median_rel_err": float(np.median(rel)) if rel else 0.0,
            "ci_coverage": covered / len(items)}
