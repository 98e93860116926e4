#!/usr/bin/env python3
"""Smoke run of the AQP serving path on a TPU, through its normal entry point.

    python3 chip_smoke.py             # one chip: ingest, fit, serve, check
    python3 chip_smoke.py --chips 4   # the sharded bandwidth selectors only

One chip: 4,194,304 synthetic telemetry rows go into a
`TelemetryStore(capacity=32768)` exactly as `serve --mode aqp` seeds it
(`repro.launch.serve.seed_aqp_store`).  `serve.run_aqp` then fits the
synopses on the chip (PLUGIN per column and per joint axis, LSCV_H on the
(loss, latency_ms) joint, the RFF density synopsis above the crossover) and
answers 512 mixed queries from 8 client threads through one `AqpSession`
with the producer quiescent, plus a GROUP BY — once with the Pallas kernels
and once with the jnp path.  A per-query `selector="lscv_h"` batch fits
LSCV_h on the 1-D reservoirs, and the remaining kernels are compared with
their jnp counterparts.  The checks:

  * every result path was served by both backends, and every Pallas kernel
    it expects was dispatched compiled (never interpreted);
  * Pallas and jnp answers agree within the bound the in-kernel erf allows
    (see `_agree_bound`), the shared paths exactly or to f32 summation;
  * the LSCV_h objective on a 2,048-row subsample matches the float64 host
    oracle `g_of_h_sequential` (rtol 2e-3, as tests/test_lscv.py holds it).

Four chips: `sharded_pairwise_reduce`, `sharded_plugin_psi_sums` and
`distributed_lscv_h` on a 4-device mesh at n=65,536 (strided rows,
replicated sample) against the same selectors on one chip; every device
must contribute a partial.

Timings printed are smoke timings of one cold run, compilation included —
not benchmark numbers.  The last line of stdout is one JSON object,
{"ok": true, "device": {...}}; it is printed only when every phase passed
on a TPU.  Exits non-zero, printing no such line, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ROWS = 4_194_304          # telemetry rows ingested
CAPACITY = 32_768         # reservoir rows kept on the device per column
CLIENTS = 8
PER_CLIENT = 64           # 8 x 64 = 512 mixed queries per backend
SUBSAMPLE = 2_048         # rows for the float64 LSCV_h oracle
SHARDED_N = 65_536        # rows for the four-chip selectors

EXPECTED_PATHS = ("range1d", "box", "box:grouped", "qmc", "exact")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Phases:
    """Runs named phases in order, timing each; the first failure stops."""

    def __init__(self):
        self.done = []

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
        self.done.append((name, dt))
        log(f"PASS {name} (smoke timing, not a benchmark: {dt:.2f} s)")
        return out


class CompileCacheEvents:
    """Counts JAX's persistent-cache hits and misses in this process."""

    def __init__(self):
        import jax
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# --- exact reference ---------------------------------------------------------

def exact_answer(q, cols, group=None) -> float:
    """The aggregate over every ingested row, in float64: the reference the
    estimates approximate (Eq and group terms are the code window +-1/2,
    as the engine defines them)."""
    import numpy as np

    from repro.core import Box, Eq, Range

    n = len(next(iter(cols.values())))
    mask = np.ones(n, bool)

    def within(col, lo, hi):
        v = cols[col]
        return (v >= lo) & (v <= hi)

    pred_cols = []
    for t in q.predicates:
        if isinstance(t, Range):
            mask &= within(t.column, t.a, t.b)
            pred_cols.append(t.column)
        elif isinstance(t, Eq):
            mask &= within(t.column, t.value - t.halfwidth,
                           t.value + t.halfwidth)
            pred_cols.append(t.column)
        else:
            assert isinstance(t, Box)
            for c, lo, hi in zip(t.columns, t.lo, t.hi):
                mask &= within(c, lo, hi)
                pred_cols.append(c)
    if group is not None:
        mask &= within(q.group_by.column, group - 0.5, group + 0.5)
    count = float(np.count_nonzero(mask))
    if q.aggregate == "count":
        return count
    target = q.target if q.target is not None else pred_cols[0]
    total = float(np.sum(cols[target][mask], dtype=np.float64))
    if q.aggregate == "sum":
        return total
    return total / count if count else 0.0


def accuracy(results, cols) -> dict:
    """Relative error of every estimate against the exact aggregate, and
    the share of 95% intervals that contain it."""
    import numpy as np

    rel, covered = [], 0
    for r in results:
        truth = exact_answer(r.query, cols, r.group)
        covered += int(r.ci_lo <= truth <= r.ci_hi)
        if truth != 0.0:
            rel.append(abs(r.estimate - truth) / abs(truth))
    rel = np.asarray(rel)
    return {"n": len(results), "median_rel_err": float(np.median(rel)),
            "max_rel_err": float(np.max(rel)),
            "ci_coverage": covered / len(results)}


# --- Pallas vs jnp agreement -------------------------------------------------

def _agree_bound(store, res_p, count_p) -> float:
    """Largest |pallas - jnp| the two backends may differ by for one query.

    Closed forms (range1d, box, box:grouped): the kernels evaluate erf with
    `kernels/erf.py` (within ERF_ABS_ERR of the true erf), the jnp path with
    XLA's; a per-axis Phi difference then differs by at most
    e1 = 2 ERF_ABS_ERR + 2^-23 per sample (two erf terms, halved, on each
    side, plus f32 rounding of Phi near 1).  A d-axis product of factors in
    [0, 1] differs by at most d e1, summed over the sample and scaled to
    the relation: n_source d e1 for COUNT, times max|x_t| + h_t (the
    first-moment factor's size) for SUM, and the propagated ratio error for
    AVG.  On top, 2e-5 relative for f32 accumulation in different orders
    (at most ~(tiles + log2 tile) 2^-24 per side).
    The RFF density pass is one kernel on both backends and the exact
    sketch path is shared, so those agree to 1e-5 relative; the exact QMC
    pass (kernel vs jnp, both f32 over the same Halton nodes) to 1e-4.
    """
    from repro.kernels.erf import ERF_ABS_ERR

    path = res_p.path.split(":pallas")[0]
    est = abs(res_p.estimate)
    if path == "exact":
        return 0.0
    if path.startswith("qmc"):
        return (1e-5 if path == "qmc:rff" else 1e-4) * est
    q = res_p.query
    e1 = 2 * ERF_ABS_ERR + 2.0 ** -23
    if path == "range1d":
        col = q.predicates[0].column
        syn = store.synopsis(col, q.selector or "plugin")
        d, target_syn, axis = 1, syn, 0
        x_t = syn.x
    else:
        joint = ("model_id", "latency_ms") if q.group_by is not None \
            else ("loss", "latency_ms")
        syn = store.joint_synopsis(joint, q.selector or "plugin")
        d, target_syn = 2, syn
        tname = q.target if q.target is not None else joint[0]
        axis = joint.index(tname)
        x_t = syn.x[:, axis]
    import numpy as np
    h_t = float(np.asarray(target_syn.h_diag())[axis]) if path != "range1d" \
        else float(target_syn.h)
    m_t = float(np.max(np.abs(np.asarray(x_t)))) + h_t
    cnt_tol = syn.n_source * d * e1
    sum_tol = cnt_tol * m_t
    if q.aggregate == "count":
        tol = cnt_tol
    elif q.aggregate == "sum":
        tol = sum_tol
    else:
        tol = (sum_tol + est * cnt_tol) / max(count_p, 1e-3)
    return tol + 2e-5 * est


def count_twins(engine, results):
    """COUNT under each AVG result's predicates (same selector, same group):
    the denominator the AVG agreement bound divides by."""
    from repro.core import AqpQuery

    counts = {}
    avg = [r for r in results if r.query.aggregate == "avg"
           and r.path.split(":")[0] in ("range1d", "box")]
    seen = {}
    for r in avg:
        q = r.query
        if id(q) in seen:
            continue
        seen[id(q)] = AqpQuery("count", q.predicates, group_by=q.group_by,
                               selector=q.selector,
                               kde_backend=q.kde_backend)
    if not seen:
        return counts
    keys = list(seen)
    twins = engine.execute([seen[k] for k in keys])
    by_query = {}
    for r in twins:
        by_query.setdefault(id(r.query), []).append(r)
    for k in keys:
        for r in by_query[id(seen[k])]:
            counts[(k, r.group)] = r.estimate
    return counts


def compare_backends(store, res_p, res_j, label: str) -> float:
    """Check Pallas against jnp answer by answer; returns the largest
    |difference| / bound seen.

    A quasi-MC answer depends on the whole micro-batch it was flushed with
    (the batch's bounding box places the shared nodes), and admission
    batches differ run to run; so the full-H queries are re-asked as one
    batch on each backend and those answers compared instead."""
    check(len(res_p) == len(res_j), f"{label}: {len(res_p)} pallas vs "
          f"{len(res_j)} jnp answers")
    qmc = [(rp.query, rj.query) for rp, rj in zip(res_p, res_j)
           if rp.path.startswith("qmc")]
    if qmc:
        queries = [qp for qp, _qj in qmc]
        res_p = [r for r in res_p if not r.path.startswith("qmc")] \
            + store.engine(backend="pallas").execute(queries)
        res_j = [r for r in res_j if not r.path.startswith("qmc")] \
            + store.engine(backend="jnp").execute(queries)
    counts = count_twins(store.engine(backend="pallas"), res_p)
    worst = 0.0
    for rp, rj in zip(res_p, res_j):
        check(rp.query == rj.query and rp.group == rj.group,
              f"{label}: answers out of order")
        check(rp.path.replace(":pallas", "") == rj.path,
              f"{label}: path {rp.path} vs {rj.path}")
        check(math.isfinite(rp.estimate) and math.isfinite(rj.estimate),
              f"{label}: non-finite estimate for {rp.query}")
        bound = _agree_bound(store, rp, counts.get((id(rp.query), rp.group),
                                                   0.0))
        diff = abs(rp.estimate - rj.estimate)
        check(diff <= bound, f"{label}: {rp.path} pallas {rp.estimate!r} vs "
              f"jnp {rj.estimate!r} (|diff| {diff:.3g} > bound {bound:.3g}) "
              f"for {rp.query}")
        if bound > 0:
            worst = max(worst, diff / bound)
    return worst


# --- one chip ----------------------------------------------------------------

def serve_args(backend: str):
    from repro.launch import serve

    return serve.parse_args([
        "--mode", "aqp", "--rows", str(ROWS), "--capacity", str(CAPACITY),
        "--clients", str(CLIENTS), "--per-client", str(PER_CLIENT),
        "--stream-every-ms", "1e9",           # producer quiescent
        "--fullh-frac", "0.25", "--backend", backend])


def lscv_h_override(store, backend: str, cols):
    """1-D ranges with a per-query selector="lscv_h" override: LSCV_h fits
    on the full reservoirs, answered through an AqpSession."""
    import numpy as np

    from repro.core import AqpQuery, Range

    rng = np.random.default_rng(7)
    specs = []
    for col in ("loss", "latency_ms", "seq_len"):
        lo, hi = float(np.min(cols[col])), float(np.max(cols[col]))
        for op in ("count", "sum", "avg"):
            a = float(rng.uniform(lo, hi))
            specs.append(AqpQuery(op, (Range(col, a,
                                             float(rng.uniform(a, hi))),),
                                  target=None if op == "count" else col,
                                  selector="lscv_h"))
    session = store.engine(backend=backend).session(watermark=len(specs),
                                                    max_delay=0.05)
    try:
        futures = [session.submit(q) for q in specs]
        return [f.result() for f in futures]
    finally:
        session.close()


def kernels_vs_jnp(store) -> dict:
    """Fit-side kernels, each against its jnp counterpart on the chip:
    PLUGIN (pairwise_reduce, which a pallas engine's fits also run) on a
    full reservoir, kde_eval, and the LSCV_H objective (gh_fused) at the
    fitted H."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kde_eval, plugin_bandwidth
    from repro.core.lscv import g_of_H

    x = jnp.asarray(store.columns["loss"].sample(), jnp.float32)
    hp = float(plugin_bandwidth(x, backend="pallas").h)
    hj = float(plugin_bandwidth(x, backend="jnp").h)
    check(abs(hp - hj) <= 1e-3 * abs(hj),
          f"PLUGIN h pallas {hp} vs jnp {hj} (n={x.shape[0]})")

    joint = store.joint_synopsis(("loss", "latency_ms"), "lscv_H")
    xs = jnp.asarray(joint.x, jnp.float32)
    pts = xs[:4096]
    h = jnp.float32(float(np.asarray(store.joint_synopsis(
        ("loss", "latency_ms"), "plugin").h_diag())[0]))
    fp = np.asarray(kde_eval(pts, xs, h, backend="pallas"), np.float64)
    fj = np.asarray(kde_eval(pts, xs, h, backend="jnp"), np.float64)
    kde_err = float(np.max(np.abs(fp - fj)) / np.max(np.abs(fj)))
    check(kde_err <= 1e-4, f"kde_eval pallas vs jnp max rel err {kde_err}")

    gp = float(g_of_H(xs, joint.H, backend="pallas"))
    gj = float(g_of_H(xs, joint.H, backend="jnp"))
    check(abs(gp - gj) <= 2e-3 * abs(gj),
          f"LSCV_H objective pallas {gp} vs jnp {gj}")
    return {"plugin_h": (hp, hj), "kde_max_rel_err": kde_err,
            "g_H": (gp, gj)}


def lscv_h_objective(store) -> dict:
    """LSCV_h on a 2,048-row subsample of the (loss, latency_ms) joint: the
    Pallas (streaming lscv_grid) and jnp objectives against the
    float64 host oracle at the chosen h and the grid's first point."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lscv_h
    from repro.core.lscv import g_of_h_sequential

    x = np.asarray(store.joints[("loss", "latency_ms")].sample(), np.float32)
    idx = np.random.default_rng(3).choice(x.shape[0], SUBSAMPLE,
                                          replace=False)
    sub = jnp.asarray(x[idx])
    rp = lscv_h(sub, backend="pallas")
    rj = lscv_h(sub, backend="jnp")
    gp = np.asarray(rp.g_values, np.float64)
    gj = np.asarray(rj.g_values, np.float64)
    check(np.allclose(gp, gj, rtol=3e-4),
          f"LSCV_h objective pallas vs jnp: max rel "
          f"{float(np.max(np.abs(gp - gj) / np.abs(gj)))}")
    grid = np.asarray(rp.h_grid, np.float64)
    worst = 0.0
    for i in sorted({int(np.argmin(gp)), 0}):
        oracle = g_of_h_sequential(np.asarray(sub), float(grid[i]))
        for name, g in (("pallas", gp[i]), ("jnp", gj[i])):
            err = abs(g - oracle) / abs(oracle)
            check(err <= 2e-3, f"LSCV_h g({grid[i]:.4g}) {name} {g} vs "
                  f"float64 oracle {oracle} (rel err {err:.3g})")
            worst = max(worst, err)
    return {"h": float(rp.h), "max_rel_err_vs_oracle": worst}


def kernel_dispatches() -> dict:
    """{kernel: {"compiled": n, "interpreted": n}} from the obs registry."""
    from repro import obs

    out = {}
    for labels, v in obs.get_registry().collect_counters("kernel.calls"):
        if labels.get("autotune") == "sweep":
            continue
        mode = "interpreted" if labels.get("interpret") == "True" \
            else "compiled"
        ent = out.setdefault(labels.get("kernel", "?"),
                             {"compiled": 0, "interpreted": 0})
        ent[mode] += int(v)
    return out


def run_one_chip(phases: Phases) -> None:
    from repro import obs
    from repro.launch import serve

    obs.enable()      # kernel dispatch counters record compiled vs interpreted
    args_p = serve_args("pallas")
    seeded = phases.run(f"ingest {ROWS:,} rows into TelemetryStore("
                        f"capacity={CAPACITY})", serve.seed_aqp_store, args_p)
    store, _n, _step, cols = seeded

    n_q = CLIENTS * PER_CLIENT
    out_p = phases.run(f"fit + serve {n_q} queries + GROUP BY [pallas]",
                       serve.run_aqp, args_p, seeded)
    out_j = phases.run(f"serve {n_q} queries + GROUP BY [jnp]",
                       serve.run_aqp, serve_args("jnp"), seeded)
    lp = phases.run("fit lscv_h + serve per-query overrides [pallas]",
                    lscv_h_override, store, "pallas", cols)
    lj = phases.run("serve per-query lscv_h overrides [jnp]",
                    lscv_h_override, store, "jnp", cols)
    kern = phases.run("kernels vs jnp (pairwise_reduce, kde_eval, gh_fused)",
                      kernels_vs_jnp, store)
    log(f"PLUGIN h pallas/jnp {kern['plugin_h']}, kde_eval max rel err "
        f"{kern['kde_max_rel_err']:.3g}, LSCV_H g pallas/jnp {kern['g_H']}")
    lo = phases.run(f"LSCV_h objective on {SUBSAMPLE} rows vs float64 oracle",
                    lscv_h_objective, store)
    log(f"LSCV_h h={lo['h']:.5g}, max rel err vs g_of_h_sequential "
        f"{lo['max_rel_err_vs_oracle']:.3g} (rtol 2e-3)")

    res_p = out_p["results"] + out_p["group_by"] + lp
    res_j = out_j["results"] + out_j["group_by"] + lj

    def check_all():
        for backend, res in (("pallas", res_p), ("jnp", res_j)):
            paths = sorted({r.path for r in res})
            log(f"paths [{backend}]: {paths}")
            for want in EXPECTED_PATHS:
                full = want if backend == "jnp" or want == "exact" \
                    else f"{want}:{backend}"
                seen = any(p == full or (want == "qmc" and p == "qmc:rff")
                           for p in paths)
                check(seen, f"no query was served on path {full!r} "
                      f"[{backend}]")
        disp = kernel_dispatches()
        log(f"kernel dispatches: {disp}")
        for name in ("aqp_batch_sums", "aqp_box_sums", "aqp_grouped_sums",
                     "pairwise_scaled_ksum", "kde_eval", "gh_fused_sum",
                     "lscv_grid_sums"):
            ent = disp.get(name, {"compiled": 0, "interpreted": 0})
            check(ent["compiled"] > 0, f"kernel {name} never ran compiled")
        check(disp.get("rff_density", {}).get("compiled", 0) > 0
              or disp.get("qmc_box_reduce", {}).get("compiled", 0) > 0,
              "neither rff_density nor qmc_box_reduce ran compiled")
        bad = {k: v for k, v in disp.items() if v["interpreted"]}
        check(not bad, f"kernels ran in interpret mode: {bad}")
        worst = compare_backends(store, res_p, res_j, "pallas vs jnp")
        log(f"pallas vs jnp: {len(res_p)} answers agree; largest "
            f"|diff| / bound = {worst:.3g}")
        acc = accuracy(res_p, cols)
        log(f"accuracy vs exact aggregate over all {ROWS:,} rows "
            f"({acc['n']} answers): median rel err "
            f"{acc['median_rel_err']:.4g}, max rel err "
            f"{acc['max_rel_err']:.4g}; 95% CI contains the exact answer "
            f"for {acc['ci_coverage']:.1%}")
        fb = store.metrics.sum_counter("aqp.synopsis.fallback")
        log(f"aqp.synopsis.fallback = {fb}")

    phases.run("checks", check_all)


# --- four chips --------------------------------------------------------------

def run_four_chips(phases: Phases) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core import gaussian as G
    from repro.core import plugin_bandwidth
    from repro.core.distributed import (_strided_pairwise_partial,
                                        distributed_lscv_h,
                                        sharded_pairwise_reduce,
                                        sharded_plugin_psi_sums)
    from repro.launch.serve import _make_telemetry

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh4 = jax.make_mesh((4,), ("data",), devices=devs[:4])
    mesh1 = jax.make_mesh((1,), ("data",), devices=devs[:1])
    cols = _make_telemetry(np.random.default_rng(0), SHARDED_N)
    x1 = jnp.asarray(cols["loss"])
    x2 = jnp.asarray(np.stack([cols["loss"], cols["latency_ms"]], axis=1))
    pb = plugin_bandwidth(x1)
    g1, g2, h = pb.g1, pb.g2, pb.h
    fun = lambda d: G.phi(d / h)              # the KDE's own pair sum

    def selectors(mesh):
        pair = float(sharded_pairwise_reduce(fun, x1, mesh))
        s6, s4 = (float(v) for v in sharded_plugin_psi_sums(x1, g1, g2,
                                                            mesh))
        h, _grid, g = distributed_lscv_h(x2, mesh)
        return {"pair": pair, "s6": s6, "s4": s4, "h": float(h),
                "g": np.asarray(g, np.float64)}

    four = phases.run(f"sharded selectors on 4 chips (n={SHARDED_N:,})",
                      selectors, mesh4)
    one = phases.run(f"same selectors on 1 chip (n={SHARDED_N:,})",
                     selectors, mesh1)

    def per_device():
        f = compat.shard_map(
            lambda xr: _strided_pairwise_partial(
                fun, xr, jax.lax.axis_index(("data",)), 4, 256,
                ("data",))[None],
            mesh=mesh4, in_specs=P(), out_specs=P("data"))
        parts = f(x1)
        return parts, {d.id for d in parts.sharding.device_set}

    parts, dev_ids = phases.run("per-device partials on 4 chips", per_device)

    def check_all():
        for k in ("pair", "s6", "s4"):
            a, b = four[k], one[k]
            check(abs(a - b) <= 1e-3 * abs(b), f"{k}: 4 chips {a} vs 1 chip {b}")
        check(np.allclose(four["g"], one["g"], rtol=2e-3),
              "LSCV_h objective: 4 chips vs 1 chip")
        # the chosen h may differ only where the objective is flat: the two
        # minima must agree to the objective's own tolerance
        g4, g1 = float(np.min(four["g"])), float(np.min(one["g"]))
        check(four["h"] == one["h"] or abs(g4 - g1) <= 2e-3 * abs(g1),
              f"LSCV_h h: {four['h']} vs {one['h']}")
        p = np.asarray(parts, np.float64)
        log(f"per-device pair-sum partials on devices {sorted(dev_ids)}: "
            f"{p.tolist()} (sum {float(p.sum())!r}, sharded "
            f"{four['pair']!r})")
        check(len(dev_ids) == 4, f"partials live on {len(dev_ids)} devices")
        check(bool(np.all(np.abs(p) > 0)), "a device contributed no work")
        check(abs(p.sum() - four["pair"]) <= 1e-3 * abs(four["pair"]),
              "per-device partials do not sum to the sharded result")
        log(f"4 chips vs 1 chip: pairwise {four['pair']!r} / {one['pair']!r}, "
            f"psi6 sum {four['s6']!r} / {one['s6']!r}, psi4 sum "
            f"{four['s4']!r} / {one['s4']!r}, LSCV_h h {four['h']!r}, max "
            f"g rel diff "
            f"{float(np.max(np.abs(four['g'] - one['g']) / np.abs(one['g']))):.3g}")

    phases.run("checks", check_all)


# --- entry point -------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the AQP serving path; 4: the sharded bandwidth "
                         "selectors against one chip, and nothing else")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind}); this smoke runs only "
              f"on a TPU", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: the repro package is not under {src}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache = CompileCacheEvents()
    log(f"device: {dev.platform} {dev.device_kind}, {len(devices)} "
        f"device(s) visible; compile cache at {cache_dir}")

    phases = Phases()
    try:
        if args.chips == 4:
            run_four_chips(phases)
        else:
            run_one_chip(phases)
    except Exception as exc:        # report the phase that failed, exit 1
        traceback.print_exc()
        print(f"chip_smoke: FAILED after {len(phases.done)} phase(s): "
              f"{type(exc).__name__}: {exc}", file=sys.stderr)
        return 1

    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses "
        f"({'hit' if cache.hits else 'not hit'})")
    log(f"all {len(phases.done)} phases passed; total "
        f"{sum(t for _n, t in phases.done):.1f} s (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
