"""Shape-keyed tile autotuner for the Pallas kernels.

PR 7 built the measurement half (`tuning.profiled_call` records fenced
per-shape wall timings; `tuning.measured()` reads them back).  This module
is the decision half: `sweep()` times a kernel over candidate tile
configurations for one (kernel, n, d, G, ...) shape — every candidate runs
through `profiled_call`, so sweep measurements land in the same
`kernel.wall_us` instrument the serving stack exports — and caches the
winner.  The current env/default configuration is always candidate #0, so
the chosen tiles are never slower than the defaults *on the swept
timings* (`entry["us"] <= entry["default_us"]` by construction; CI asserts
it through `scripts/validate_metrics.py --tuning`).

Resolution order in the ops.py wrappers (via `resolve()`):

    explicit kwarg  >  tile cache (this module)  >  env var  >  default

Shape keys bucket `n`/`G`/`m`-like sizes to the next power of two (`d` stays
exact) — the engine already quantizes batch shapes (`aqp_query._pad_count`),
so one swept entry covers the whole bucket instead of demanding an exact
size match.

Persistence: `REPRO_TUNING_CACHE=/path/tiles.json` makes every sweep
persist its choice and makes a fresh process load the file lazily on first
lookup — zero re-sweeps on restart (test-enforced).  `scripts/autotune.py`
is the CLI: it sweeps the shapes `tuning.measured()` (or a `--metrics`
snapshot) says the workload actually ran.

Instruments (process-global registry): `autotune.sweeps` counter and
`autotune.sweep_us` histogram per kernel, `autotune.cache.hits` /
`autotune.cache.misses` counters per kernel (only once a cache is active —
the no-cache fast path stays counter-free), `autotune.cache.entries` gauge.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import knobs, obs

from .tuning import interpret, profiled_call, resolve_tile, row_tile

_SCHEMA_VERSION = 1

_lock = threading.Lock()
_tiles: Dict[str, Dict[str, int]] = {}     # shape key -> winning tile dict
_entries: Dict[str, dict] = {}             # shape key -> full sweep record
_loaded_from: Optional[str] = None         # path probed for REPRO_TUNING_CACHE


def _bucket(v: int) -> int:
    v = int(v)
    return v if v <= 1 else 1 << (v - 1).bit_length()


def shape_key(kernel: str, shape: Dict[str, int]) -> str:
    """Canonical cache key: kernel name plus sorted shape labels, sizes
    bucketed to the next power of two (`d` exact — it changes the kernel's
    unrolled body, not just the grid)."""
    parts = [kernel]
    for k in sorted(shape):
        v = int(shape[k])
        parts.append(f"{k}={v if k == 'd' else _bucket(v)}")
    return "|".join(parts)


def reset() -> None:
    """Drop all in-process tuner state (tests simulate a fresh process)."""
    global _loaded_from
    with _lock:
        _tiles.clear()
        _entries.clear()
        _loaded_from = None


def _ensure_loaded() -> None:
    global _loaded_from
    path = knobs.get_str("REPRO_TUNING_CACHE")
    with _lock:
        if _loaded_from == path:
            return
        _loaded_from = path
    if path and os.path.exists(path):
        load_cache(path)


def lookup(kernel: str, shape: Dict[str, int]) -> Optional[Dict[str, int]]:
    """Cached tile choice for a shape, or None.  Hot path: one dict probe
    when no cache is active (no counters, no env churn)."""
    _ensure_loaded()
    if not _tiles:
        return None
    with _lock:
        hit = _tiles.get(shape_key(kernel, shape))
    reg = obs.get_registry()
    if hit is None:
        reg.counter("autotune.cache.misses", kernel=kernel).inc()
        return None
    reg.counter("autotune.cache.hits", kernel=kernel).inc()
    return hit


def resolve(kernel: str, shape: Dict[str, int], **params) -> Tuple[int, ...]:
    """Resolve tile parameters for one kernel dispatch.

    `params` maps each tile name to (override, env_name, default); returns
    the resolved values in declaration order.  Explicit kwarg > cached
    sweep winner > env var > default (`tuning.resolve_tile`).
    """
    cached = None
    if not all(ov is not None for ov, _e, _d in params.values()):
        cached = lookup(kernel, shape)
    out = []
    for name, (override, env_name, default) in params.items():
        if override is not None:
            out.append(int(override))
        elif cached is not None and name in cached:
            out.append(int(cached[name]))
        else:
            out.append(resolve_tile(env_name, default))
    return tuple(out)


def record(kernel: str, shape: Dict[str, int], tiles: Dict[str, int],
           entry: Optional[dict] = None) -> str:
    """Install a tile choice in the in-process cache; returns its key."""
    key = shape_key(kernel, shape)
    with _lock:
        _tiles[key] = {k: int(v) for k, v in tiles.items()}
        if entry is not None:
            _entries[key] = entry
    obs.get_registry().gauge("autotune.cache.entries").set(len(_tiles))
    return key


def save_cache(path: str) -> dict:
    """Atomically write every recorded sweep entry as the tile-cache JSON
    (`scripts/validate_metrics.py --tuning` checks this schema)."""
    with _lock:
        entries = [dict(e) for e in _entries.values()]
    doc = {"version": _SCHEMA_VERSION, "ts": time.time(), "entries": entries}
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return doc


def load_cache(path: str) -> int:
    """Merge a persisted tile cache into the in-process state; returns the
    number of entries loaded.  Malformed files fail loudly — a silently
    ignored cache would re-sweep on every restart."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("version") != _SCHEMA_VERSION:
        raise ValueError(f"{path}: unsupported tile-cache version "
                         f"{doc.get('version')!r}")
    n = 0
    for e in doc.get("entries", ()):
        record(str(e["kernel"]), {k: int(v) for k, v in e["shape"].items()},
               {k: int(v) for k, v in e["tiles"].items()}, entry=e)
        n += 1
    return n


# --- sweeping ---------------------------------------------------------------

def _dedupe(cands: Sequence[Dict[str, int]]) -> List[Dict[str, int]]:
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def _eff(tile: int, size: int) -> int:
    """The tile size the kernel will actually use after pow2 rounding —
    candidates that collapse to the same effective tiles are duplicates."""
    return row_tile(tile, size)


def _grid(shape: Dict[str, int], axes: Dict[str, Tuple[str, Sequence[int]]],
          defaults: Dict[str, int], quick: bool) -> List[Dict[str, int]]:
    """Candidate tile dicts: the env/default configuration first, then the
    cross product of per-axis candidates (quick mode: defaults plus the
    per-axis extremes), deduped by effective tile size."""
    names = list(axes)
    cands = [dict(defaults)]
    pools = []
    for name in names:
        size_label, pool = axes[name]
        pool = sorted({_eff(t, shape[size_label]) for t in pool})
        if quick:
            pool = sorted({pool[0], pool[-1],
                           _eff(defaults[name], shape[size_label])})
        pools.append(pool)

    def rec(i, acc):
        if i == len(names):
            cands.append(dict(acc))
            return
        for t in pools[i]:
            acc[names[i]] = t
            rec(i + 1, acc)
        del acc[names[i]]

    rec(0, {})
    eff = []
    for c in cands:
        eff.append({n: _eff(c[n], shape[axes[n][0]]) for n in names})
    # dedupe on effective tiles, keeping first occurrence (defaults win ties)
    seen, out = set(), []
    for c, e in zip(cands, eff):
        key = tuple(sorted(e.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


class _Sweep:
    def __init__(self, params, defaults, candidates, make):
        self.params = params          # tile kwarg names, in order
        self.defaults = defaults      # () -> {name: env/default value}
        self.candidates = candidates  # (shape, quick) -> [tile dict, ...]
        self.make = make              # shape -> callable(tiles) running once


def _make_aqp_batch(shape):
    import jax.numpy as jnp
    from . import aqp_batch as m
    rng = np.random.default_rng(0)
    n, G = shape["n"], shape["G"]
    x = jnp.asarray(rng.normal(0, 2, n).astype(np.float32))
    a = jnp.asarray(rng.uniform(-4, 2, G).astype(np.float32))
    b = a + jnp.asarray(rng.uniform(0.2, 3, G).astype(np.float32))
    h = jnp.float32(0.5)
    interp = interpret()
    return lambda t: m.aqp_batch_sums(x, h, a, b, interpret=interp, **t)


def _make_aqp_boxes(shape):
    import jax.numpy as jnp
    from . import aqp_boxes as m
    rng = np.random.default_rng(0)
    n, d, G = shape["n"], shape["d"], shape["G"]
    x = jnp.asarray(rng.normal(0, 1.5, (n, d)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.2, 0.8, d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(-3, 1, (G, d)).astype(np.float32))
    hi = lo + jnp.asarray(rng.uniform(0.2, 3, (G, d)).astype(np.float32))
    tgt = jnp.asarray(rng.integers(0, d, G), jnp.int32)
    interp = interpret()
    return lambda t: m.aqp_box_sums(x, h, lo, hi, tgt, interpret=interp, **t)


def _make_aqp_grouped(shape):
    import jax.numpy as jnp
    from . import aqp_grouped as m
    rng = np.random.default_rng(0)
    n, d, G = shape["n"], shape["d"], shape["G"]
    x = jnp.asarray(rng.normal(0, 1.5, (n, d)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.2, 0.8, d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(-3, -1, d).astype(np.float32))
    hi = lo + 4.0
    glo = jnp.asarray(np.arange(G, dtype=np.float32) - 0.5)
    ghi = glo + 1.0
    interp = interpret()
    return lambda t: m.aqp_grouped_sums(x, h, lo, hi, glo, ghi, g_axis=0,
                                        tgt=min(1, d - 1),
                                        interpret=interp, **t)


def _make_qmc_reduce(shape):
    import jax.numpy as jnp
    from . import qmc_reduce as m
    rng = np.random.default_rng(0)
    n, d, G = shape["n"], shape["d"], shape["G"]
    nm = shape.get("m", 1024)
    x = jnp.asarray(rng.normal(0, 1.0, (n, d)).astype(np.float32))
    nodes = jnp.asarray(rng.uniform(-3, 3, (nm, d)).astype(np.float32))
    h_inv = jnp.asarray(np.eye(d, dtype=np.float32) * 4.0)
    log_norm = jnp.float32(-0.5 * d)
    lo = jnp.asarray(rng.uniform(-3, 0, (G, d)).astype(np.float32))
    hi = lo + 2.0
    tgt = jnp.asarray(rng.integers(0, d, G), jnp.int32)
    interp = interpret()
    return lambda t: m.qmc_box_reduce(nodes, x, h_inv, log_norm, lo, hi,
                                      tgt, interpret=interp, **t)


def _rff_defaults():
    return {"tile": resolve_tile("REPRO_RFF_TILE", 512),
            "p_tile": resolve_tile("REPRO_RFF_P_TILE", 256)}


def _make_rff(shape):
    import jax.numpy as jnp
    from . import rff_eval as m
    rng = np.random.default_rng(0)
    n, d, G = shape["n"], shape["d"], shape["G"]    # n: features, G: points
    pts = jnp.asarray(rng.normal(0, 1, (G, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 6.28, n).astype(np.float32))
    z = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    interp = interpret()
    return lambda t: m.rff_density(pts, w, b, z, interpret=interp, **t)


_POOL = (128, 256, 512, 1024)     # lane axes: Mosaic blocks are 128-multiples
_QPOOL = (16, 32, 64, 128, 256)

SWEEPS: Dict[str, _Sweep] = {
    "aqp_batch_sums": _Sweep(
        ("tile", "q_tile"),
        lambda: {"tile": resolve_tile("REPRO_AQP_TILE", 256),
                 "q_tile": resolve_tile("REPRO_AQP_Q_TILE", 128)},
        lambda shape, quick: _grid(
            shape, {"tile": ("n", _POOL), "q_tile": ("G", _QPOOL)},
            {"tile": resolve_tile("REPRO_AQP_TILE", 256),
             "q_tile": resolve_tile("REPRO_AQP_Q_TILE", 128)}, quick),
        _make_aqp_batch),
    "aqp_box_sums": _Sweep(
        ("tile", "q_tile"),
        lambda: {"tile": resolve_tile("REPRO_AQP_BOXES_TILE", 128),
                 "q_tile": resolve_tile("REPRO_AQP_BOXES_Q_TILE", 64)},
        lambda shape, quick: _grid(
            shape, {"tile": ("n", _POOL), "q_tile": ("G", _QPOOL)},
            {"tile": resolve_tile("REPRO_AQP_BOXES_TILE", 128),
             "q_tile": resolve_tile("REPRO_AQP_BOXES_Q_TILE", 64)}, quick),
        _make_aqp_boxes),
    "aqp_grouped_sums": _Sweep(
        ("tile", "g_tile"),
        lambda: {"tile": resolve_tile("REPRO_AQP_GROUPED_TILE", 128),
                 "g_tile": resolve_tile("REPRO_AQP_GROUPED_G_TILE", 64)},
        lambda shape, quick: _grid(
            shape, {"tile": ("n", _POOL), "g_tile": ("G", _QPOOL)},
            {"tile": resolve_tile("REPRO_AQP_GROUPED_TILE", 128),
             "g_tile": resolve_tile("REPRO_AQP_GROUPED_G_TILE", 64)}, quick),
        _make_aqp_grouped),
    "qmc_box_reduce": _Sweep(
        ("tile", "m_tile", "q_tile"),
        lambda: {"tile": resolve_tile("REPRO_QMC_TILE", 256),
                 "m_tile": resolve_tile("REPRO_QMC_M_TILE", 256),
                 "q_tile": resolve_tile("REPRO_QMC_Q_TILE", 64)},
        lambda shape, quick: _grid(
            shape, {"tile": ("n", (128, 256, 512)),
                    "m_tile": ("m", (128, 256, 512)),
                    "q_tile": ("G", (32, 64, 128))},
            {"tile": resolve_tile("REPRO_QMC_TILE", 256),
             "m_tile": resolve_tile("REPRO_QMC_M_TILE", 256),
             "q_tile": resolve_tile("REPRO_QMC_Q_TILE", 64)}, quick),
        _make_qmc_reduce),
    "rff_density": _Sweep(
        ("tile", "p_tile"),
        lambda: _rff_defaults(),
        lambda shape, quick: _grid(
            shape, {"tile": ("n", _POOL), "p_tile": ("G", _QPOOL)},
            _rff_defaults(), quick),
        _make_rff),
}


def sweep(kernel: str, shape: Dict[str, int], repeats: int = 3,
          quick: bool = False, persist: bool = True) -> dict:
    """Time every candidate tile configuration for (kernel, shape), record
    the winner in the in-process cache, and (when REPRO_TUNING_CACHE is
    set and `persist`) append it to the persisted tile cache.

    Every timed run goes through `tuning.profiled_call` with an
    `autotune="sweep"` label, so the measurements land in the standard
    `kernel.wall_us` instrument; the per-candidate mean is read back from
    the histogram deltas.  Returns the full sweep entry (schema of
    `scripts/validate_metrics.py --tuning`).
    """
    spec = SWEEPS.get(kernel)
    if spec is None:
        raise KeyError(f"no sweep registered for kernel {kernel!r}; "
                       f"have {sorted(SWEEPS)}")
    shape = {k: int(v) for k, v in shape.items()}
    run = spec.make(shape)
    candidates = _dedupe(spec.candidates(shape, quick))
    reg = obs.get_registry()
    was_enabled = obs.enabled()
    obs.enable()                 # profiled_call wall timings need fencing
    t_sweep = time.perf_counter()
    swept = []
    try:
        for tiles in candidates:
            labels = {**shape, **tiles, "autotune": "sweep"}
            hist = reg.histogram("kernel.wall_us", kernel=kernel, **labels)
            run(tiles)           # warm-up: jit trace excluded from timing
            c0, s0 = hist.count, hist.sum
            for _ in range(max(1, repeats)):
                profiled_call(kernel, lambda: run(tiles), **labels)
            us = (hist.sum - s0) / (hist.count - c0)
            swept.append({"tiles": dict(tiles), "us": us})
    finally:
        if not was_enabled:
            obs.disable()
    best = min(swept, key=lambda s: s["us"])
    entry = {
        "kernel": kernel, "shape": shape,
        "key": shape_key(kernel, shape),
        "tiles": dict(best["tiles"]), "us": best["us"],
        "default_tiles": dict(swept[0]["tiles"]),
        "default_us": swept[0]["us"],
        "repeats": int(max(1, repeats)), "swept": swept,
    }
    record(kernel, shape, best["tiles"], entry=entry)
    reg.counter("autotune.sweeps", kernel=kernel).inc()
    reg.histogram("autotune.sweep_us", kernel=kernel).observe(
        (time.perf_counter() - t_sweep) * 1e6)
    path = knobs.get_str("REPRO_TUNING_CACHE")
    if persist and path:
        save_cache(path)
    return entry
