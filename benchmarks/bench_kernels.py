"""Kernel-level timings: the PLUGIN pair-sum kernel against its jnp
reference at a matched size.

Interpret-mode timings measure *algorithm* cost on CPU, not TPU performance.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

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
    return out


if __name__ == "__main__":
    run()
