"""Kernel-level timings: the PLUGIN pair-sum kernel against its jnp
reference at a matched size, and the LSCV_h grid kernel on one served 1-D
fit (n = 32,768 on a TPU, 1,024 elsewhere; the 150-point grid).

Interpret-mode timings measure *algorithm* cost on CPU, not TPU performance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussian as G
from repro.core.lscv import N_H_DEFAULT, exp_scales, h_grid_for
from repro.kernels import ops, ref
from .common import emit, time_call


def run() -> dict:
    rng = np.random.default_rng(0)
    out = {}
    xg = jnp.asarray(rng.normal(0, 1, 4096).astype(np.float32))
    t_k = time_call(lambda: ops.pairwise_scaled_ksum(xg, jnp.float32(0.3), kind="k6"),
                    repeats=2)
    t_kr = time_call(lambda: ref.pairwise_scaled_ksum(xg, jnp.float32(0.3), "k6"),
                     repeats=2)
    emit("pairwise_k6_tile_n4096", t_k)
    emit("pairwise_k6_ref_n4096", t_kr)
    out["pairwise_ref_over_tile"] = t_kr / t_k
    out["lscv_grid_terms_per_s"] = _lscv_grid(rng)
    return out


def _lscv_grid(rng) -> float:
    """Seconds per call and (pair, h) terms per second of the LSCV_h grid
    sums over a `latency_ms`-shaped sample (80% N(40, 8), 20% N(160, 30))."""
    dev = jax.devices()[0]
    n = 32_768 if dev.platform == "tpu" else 1_024
    x = np.where(rng.random(n) < 0.8, rng.normal(40.0, 8.0, n),
                 rng.normal(160.0, 30.0, n))
    x = jnp.asarray(x.astype(np.float32))
    var = jnp.var(x, ddof=1)
    c_k, c_kk, _ = G.lscv_h_consts(1, var)
    scales = exp_scales(h_grid_for(n, 1, N_H_DEFAULT))
    m = (1.0 / var).reshape(1, 1)
    t = time_call(lambda: ops.lscv_grid_sums(x, m, scales, c_k, c_kk),
                  repeats=5)
    terms = n * (n - 1) // 2 * N_H_DEFAULT
    rate = terms / (t * 1e-6)
    emit(f"lscv_grid_d1_n{n}_h{N_H_DEFAULT}", t,
         f"{t * 1e-6:.4f} s, {rate:.4g} (pair, h) terms/s on "
         f"{dev.device_kind}")
    return rate


if __name__ == "__main__":
    run()
