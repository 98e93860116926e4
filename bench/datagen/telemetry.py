"""Synthetic serving telemetry (per-request loss, latency, length, model).

Copied from `repro.launch.serve._make_telemetry` (the rows `serve --mode aqp`
and `chip_smoke.py` ingest) so that a later change to the program cannot move
the benchmark's data.  Only the seed source differs: the caller's generator.
"""
from __future__ import annotations

import numpy as np


def generate(rng: np.random.Generator, rows: int, params=None) -> dict:
    n = int(rows)
    return {
        "loss": rng.gamma(3.0, 0.7, n).astype(np.float32),
        "latency_ms": np.where(rng.random(n) < 0.8, rng.normal(40, 8, n),
                               rng.normal(160, 30, n)).astype(np.float32),
        "seq_len": rng.integers(16, 2048, n).astype(np.float32),
        # dictionary-coded categorical column: which model variant served
        # the request, unit-spaced codes 0..3
        "model_id": rng.integers(0, 4, n).astype(np.float32),
    }
