"""Tile-size tuning knobs and measured-timing hooks for the Pallas kernels.

Every tunable kernel module resolves its tile sizes through `resolve_tile`
at CALL time, so `interpret=False` runs on real TPU can be tuned without
editing source — and without restarting the process:

    REPRO_AQP_TILE=512 REPRO_AQP_Q_TILE=256 python -m benchmarks.run ...

Call-site kwargs (`tile=`, `q_tile=` on the ops.py wrappers) still override
the environment; the env var only moves the *default*.  (Tiles used to be
baked into function defaults at import, which froze them before a sweep or
late env change could move them — `resolve_tile` is the one shared
call-time helper.)  On top of env/default resolution, the ops.py wrappers
consult the measured tile cache (`kernels/autotune.py`) first.

`profiled_call` is the measurement side of tuning: with `repro.obs` enabled,
every kernel dispatch records fenced wall time, dispatch time, and a call
count into the process-global metrics registry keyed by
(kernel, n, d, G, tile, ...), so an autotuner can read `measured()` data
for exactly the shapes the workload runs instead of sweeping blind.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax

from repro import knobs, obs


def env_int(name: str, default: int) -> int:
    """Positive-int env override with a loud failure on malformed values —
    a silently ignored typo in a tuning sweep wastes a TPU reservation.
    Delegates to the central knob registry (`repro.knobs`), so an
    unregistered name fails loudly too."""
    return knobs.get_int(name, default)


def resolve_tile(env_name: str, default: int, override=None) -> int:
    """One tile size, resolved at CALL time: explicit kwarg > env var >
    module default.  Kernel modules route every tile through this instead of
    baking `tile=TILE` into function defaults — an import-time default would
    freeze the value before an in-process sweep or late env change could
    move it (regression-tested in tests/test_autotune.py)."""
    if override is not None:
        value = int(override)
        if value <= 0:
            raise ValueError(f"tile override must be a positive integer, "
                             f"got {override!r}")
        return value
    return env_int(env_name, default)


def interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter: exactly when JAX's
    default backend is not a TPU.  The one decision point for every kernel
    dispatch (ops.py wrappers and autotune sweeps); a serving process that
    must run compiled kernels checks the platform itself, as chip_smoke.py
    does, rather than trusting this fallback."""
    return jax.default_backend() != "tpu"


def row_tile(tile: int, n: int) -> int:
    """Block extent along a sublane (second-to-last) axis of size n: the
    whole axis, padded to a power of two (at least 8), when it fits in one
    tile; otherwise `tile` rounded up to a multiple of 8."""
    full = max(8, 1 << (max(n, 1) - 1).bit_length())
    return full if full <= tile else -(-tile // 8) * 8


def lane_tile(tile: int, n: int) -> int:
    """Block extent along a lane (last) axis of size n: the whole padded
    axis when it fits in one tile; otherwise `tile` rounded up to a multiple
    of 128, the lane width Mosaic requires of a partial block."""
    full = max(8, 1 << (max(n, 1) - 1).bit_length())
    return full if full <= tile else -(-tile // 128) * 128


def square_tile(tile: int, n: int) -> int:
    """Side of a square tile of the implicit n x n pair matrix (the
    triangular pair kernels): the least power of two from 128, the lane
    width, that reaches `tile` or covers the n points."""
    k = 128
    while k < tile and k < n:
        k *= 2
    return k


def profiled_call(kernel: str, fn, /, *args, **labels):
    """Run `fn(*args)` recording per-shape timings into the global registry.

    Records three metrics labeled with `kernel` plus whatever shape/tile
    labels the wrapper passes (n, d, G, tile, ...):

      kernel.calls        counter   dispatches
      kernel.dispatch_us  histogram time until `fn` returns (async dispatch)
      kernel.wall_us      histogram time until results are device-ready

    The dispatch/wall split matters on real TPU: jax returns futures, so
    un-fenced timings measure Python overhead, not the kernel.  Callers use
    this only on the `obs.enabled()` branch — the disabled path calls the
    kernel directly and stays bit-and-trace-identical.
    """
    reg = obs.get_registry()
    t0 = time.perf_counter()
    out = fn(*args)
    t1 = time.perf_counter()
    obs.fence(*(out if isinstance(out, tuple) else (out,)))
    t2 = time.perf_counter()
    reg.counter("kernel.calls", kernel=kernel, **labels).inc()
    reg.histogram("kernel.dispatch_us", kernel=kernel, **labels).observe(
        (t1 - t0) * 1e6)
    reg.histogram("kernel.wall_us", kernel=kernel, **labels).observe(
        (t2 - t0) * 1e6)
    return out


def measured(kernel: str = None) -> List[Dict[str, object]]:
    """Measured kernel timings from the global registry: one row per
    (kernel, shape, tile) combination with call count and wall-time summary.
    The read API for a future autotuner and for bench reporting."""
    reg = obs.get_registry()
    match = {"kernel": kernel} if kernel is not None else {}
    rows = []
    for labels, hist in reg.collect_histograms("kernel.wall_us", **match):
        rows.append({**labels, **hist.summary()})
    rows.sort(key=lambda r: (r.get("kernel", ""), -r["count"]))
    return rows
