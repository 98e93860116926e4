"""Central registry for every ``REPRO_*`` environment knob.

The repo grew ~20 env knobs across kernels, the engine, the synopsis layer,
observability, and the benches.  Each used to be an ad-hoc
``os.environ.get`` at its call site, which meant a knob could silently fork:
two sites reading the same name with different defaults, or a renamed knob
leaving a dead read behind.  This module is the single source of truth —
one :class:`Knob` per name with its default, type, and docstring — and the
``repro.analysis`` knob-registry checker enforces that

  * every ``REPRO_*`` name referenced anywhere in src/scripts/benchmarks is
    registered here,
  * raw ``os.environ`` reads of ``REPRO_*`` names happen only in this module
    (or carry an audited ``# repro: allow[knob-registry]`` pragma), and
  * the registry and the knob table in ``docs/analysis.md`` match
    bidirectionally.

Accessors are typed and LOUD on malformed values: a silently ignored typo
in a tuning sweep wastes a TPU reservation (the same contract
``kernels/tuning.env_int`` always had — it now delegates here).  Reads are
uncached on purpose — knobs resolve at *call* time so a late env change or
an in-process sweep can move them without a restart (PR 9's import-freeze
fix depends on this).

This module imports nothing outside the standard library, so code that
runs before jax initialises can use it safely.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional

__all__ = ["KNOBS", "Knob", "get_bool", "get_int", "get_raw", "get_str",
           "register"]


@dataclass(frozen=True)
class Knob:
    """One environment knob: its name, parsed type, default, and doc."""

    name: str
    type: str           # "int" | "bool" | "str" | "path"
    default: object
    doc: str

    def __post_init__(self):
        if not self.name.startswith("REPRO_"):
            raise ValueError(f"knob {self.name!r} must start with REPRO_")
        if self.type not in ("int", "bool", "str", "path"):
            raise ValueError(f"knob {self.name}: unknown type {self.type!r}")
        if not self.doc.strip():
            raise ValueError(f"knob {self.name} needs a docstring")


KNOBS: Dict[str, Knob] = {}


def register(name: str, type: str, default: object, doc: str) -> Knob:
    """Register one knob; duplicate registration with different metadata is
    a collision (exactly the silent fork this registry exists to prevent)."""
    knob = Knob(name, type, default, doc)
    prev = KNOBS.get(name)
    if prev is not None and prev != knob:
        raise ValueError(f"knob {name!r} already registered with different "
                         f"metadata: {prev} vs {knob}")
    KNOBS[name] = knob
    return knob


def _lookup(name: str) -> Knob:
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError(
            f"unregistered knob {name!r}: add it to repro/knobs.py (and the "
            f"docs/analysis.md table) before reading it")
    return knob


def get_raw(name: str) -> Optional[str]:
    """The raw env string for a registered knob, or None when unset/empty."""
    _lookup(name)
    raw = os.environ.get(name)  # repro: allow[knob-registry] the one audited raw read behind every typed accessor
    if raw is None or not raw.strip():
        return None
    return raw


def get_int(name: str, default: Optional[int] = None) -> int:
    """Positive-int knob.  `default` overrides the registered default (the
    tile helpers pass per-kernel module constants)."""
    knob = _lookup(name)
    if knob.type != "int":
        raise TypeError(f"knob {name} is {knob.type}, not int")
    raw = get_raw(name)
    if raw is None:
        return int(knob.default if default is None else default)
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be a positive integer, got {raw!r}")
    if value <= 0:
        raise ValueError(f"{name} must be a positive integer, got {value}")
    return value


def get_bool(name: str) -> bool:
    """Flag knob: unset, empty, and "0" are False; anything else is True
    (matching the historical REPRO_OBS semantics)."""
    knob = _lookup(name)
    if knob.type != "bool":
        raise TypeError(f"knob {name} is {knob.type}, not bool")
    raw = os.environ.get(name, "")  # repro: allow[knob-registry] bool knobs must distinguish "" from "0" pre-strip
    return raw not in ("", "0")


def get_str(name: str, default: Optional[str] = None) -> str:
    """String/path knob; empty and unset both resolve to the default."""
    knob = _lookup(name)
    if knob.type not in ("str", "path"):
        raise TypeError(f"knob {name} is {knob.type}, not str/path")
    raw = get_raw(name)
    if raw is None:
        return str(knob.default if default is None else default)
    return raw


# ---------------------------------------------------------------------------
# The registry.  Keep this table in sync with docs/analysis.md (the
# knob-registry checker enforces the match bidirectionally).
# ---------------------------------------------------------------------------

register("REPRO_OBS", "bool", False,
         "Enable the gated observability layer (span tracing, fenced "
         "latency histograms, kernel profiling); see docs/observability.md.")
register("REPRO_BENCH_QUICK", "bool", False,
         "Shrink honouring bench suites to a CI-smoke configuration "
         "(set by `benchmarks.run --quick`).")
register("REPRO_TUNING_CACHE", "path", "",
         "Path of the persisted measured-tile cache (kernels/autotune.py); "
         "sweeps write it, fresh processes lazy-load it with zero re-sweeps.")

register("REPRO_KDE_CHUNK", "int", 256,
         "Evaluation-point chunk size for the exact kde_eval_H pass "
         "(core/kde.py) — bounds peak memory of the (chunk, n) kernel "
         "matrix.")
register("REPRO_KDE_CROSSOVER", "int", 16384,
         "Fitted-sample size above which kde_backend='auto' switches the "
         "full-H density pass from exact to the RFF synopsis.")
register("REPRO_RFF_FEATURES", "int", 2048,
         "Random-Fourier feature count D for the RFF density synopsis "
         "(accuracy ~ 1/sqrt(D); fit cost O(n*D)).")

register("REPRO_AQP_TILE", "int", 256,
         "Data-tile size of the aqp_batch_sums Pallas kernel.")
register("REPRO_AQP_Q_TILE", "int", 128,
         "Query-tile size of the aqp_batch_sums Pallas kernel.")
register("REPRO_AQP_BOXES_TILE", "int", 256,
         "Data-tile size of the aqp_box_sums Pallas kernel.")
register("REPRO_AQP_BOXES_Q_TILE", "int", 8,
         "Query-tile size of the aqp_box_sums Pallas kernel.")
register("REPRO_AQP_GROUPED_TILE", "int", 256,
         "Data-tile size of the aqp_grouped_sums Pallas kernel.")
register("REPRO_AQP_GROUPED_G_TILE", "int", 16,
         "Category-tile size of the aqp_grouped_sums Pallas kernel.")
register("REPRO_QMC_TILE", "int", 256,
         "Data-tile size of the qmc_box_reduce Pallas kernel.")
register("REPRO_QMC_M_TILE", "int", 128,
         "Node-tile size of the qmc_box_reduce Pallas kernel.")
register("REPRO_QMC_Q_TILE", "int", 8,
         "Box-tile size of the qmc_box_reduce Pallas kernel.")
register("REPRO_RFF_TILE", "int", 256,
         "Feature-tile size of the rff_density Pallas kernel.")
register("REPRO_RFF_P_TILE", "int", 128,
         "Point-tile size of the rff_density Pallas kernel.")

register("REPRO_PAIRWISE_TILE", "int", 4096,
         "Square tile side of the pairwise_scaled_ksum Pallas kernel "
         "(PLUGIN selector inner sums), rounded up to a power of two.")
register("REPRO_GH_TILE", "int", 256,
         "Data-tile size of the gh_fused_sum Pallas kernel (fused LSCV_H "
         "objective).")
register("REPRO_KDE_EVAL_TILE", "int", 256,
         "Data-tile size of the kde_eval Pallas kernel (grid KDE "
         "evaluation).")
register("REPRO_LSCV_TILE", "int", 1024,
         "Square tile side of the lscv_grid_sums Pallas kernel (LSCV_h "
         "grid sums), rounded up to a power of two.")
