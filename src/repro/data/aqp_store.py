"""AQP telemetry store — the paper's technique as a first-class framework
feature (DESIGN.md §4).

Training/serving telemetry columns (per-sequence loss, length, token stats)
stream in per batch; the store keeps a bounded reservoir sample per column and
fits KDE synopses with the paper's selectors on demand.  Queries (COUNT/SUM/
AVG over a range, quantile-ish fractions) are answered from the synopsis in
O(sample) instead of O(history) — and the synopsis is *mergeable* across hosts
(reservoir union), which is the property that makes this usable on a
1000-node fleet where no host sees the global stream.

Multi-column predicates need a *joint* density, which per-column reservoirs
cannot provide (they decorrelate the columns).  `track_joint` registers a
`MultiReservoir` that samples whole telemetry *rows* over a column tuple with
the same versioned, weighted-merge semantics; `joint_synopsis` fits a
diagonal-bandwidth (or full-H) synopsis over it for eq. 11 box queries.

Fitting a synopsis is the expensive step (bandwidth selection is O(sample^2)
for LSCV), so the store memoises fitted synopses in a `SynopsisCache` keyed by
(column-or-tuple, selector, reservoir version); any reservoir update bumps the
version and invalidates stale entries on the next lookup.  The cache is a
byte-bounded LRU (`max_entries` + `max_bytes`) with hit/miss/eviction
counters surfaced through `TelemetryStore.stats()`.

The store is *durable*: `to_state()`/`from_state()` round-trip every
reservoir (buffer, stream counters, version, RNG bit-generator state — so
post-restore sampling is deterministic), every categorical sketch, the joint
registrations with their backfill flags, and the fitted synopses in the
cache.  `save(path)`/`load(path)` put that state behind the atomic keep-k
`CheckpointManager` (repro.checkpoint), so a `serve --mode aqp` restart
warm-starts instead of refitting — and exact-Eq coverage, which requires a
sketch to have seen the *whole* stream, survives the restart.  Snapshots are
taken under the store's write lock, so a snapshot racing `add_batch` can
never persist a sketch that claims rows its reservoir has not seen.
"""
from __future__ import annotations

import copy
import threading
import time
import weakref
import zlib
from collections import OrderedDict
from typing import (Callable, Dict, Hashable, List, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from repro import obs
from repro.core.aqp import KDESynopsis, Query, canonical_selector
from repro.core.aqp_multid import BoxQuery
from repro.core.lscv import N_H_DEFAULT

ColumnKey = Union[str, Tuple[str, ...]]

STATE_FORMAT = 1     # bump on incompatible to_state layout changes

# Bandwidth fits (`_fit_cached`) count in the process-global registry, as the
# kernels' counters do, so that a reader with no store handle (a benchmark's
# per-layer reader) finds them.  Declared here, at zero and unlabelled, so
# that the counter is there before the process's first fit.
obs.get_registry().counter("aqp.synopsis.fits")


class Reservoir:
    """Algorithm-R reservoir sample with deterministic RNG.

    `version` counts accepted updates; synopsis caches key on it so any new
    data invalidates derived synopses.  Subclasses set `_row_shape` to sample
    composite items (MultiReservoir samples whole rows); all the acceptance
    and merge logic operates on the leading axis and is shared.
    """

    def __init__(self, capacity: int = 4096, seed: int = 0,
                 _row_shape: Tuple[int, ...] = ()):
        self.capacity = capacity
        self.rng = np.random.default_rng(seed)
        self.buf = np.empty((capacity, *_row_shape), np.float32)
        self.n_seen = 0
        self.n_filled = 0      # initialized buffer slots; < capacity after a
        self.version = 0       # merge of reservoirs with smaller samples

    def _coerce(self, values: np.ndarray) -> np.ndarray:
        return np.asarray(values, np.float32).ravel()

    def _spawn(self, seed: int) -> "Reservoir":
        return type(self)(self.capacity, seed=seed)

    def add(self, values: np.ndarray) -> None:
        values = self._coerce(values)
        if values.shape[0] == 0:
            return
        self.version += 1
        k = 0
        if self.n_filled < self.capacity and self.n_seen == self.n_filled:
            k = min(self.capacity - self.n_filled, values.shape[0])
            self.buf[self.n_filled: self.n_filled + k] = values[:k]
            self.n_filled += k
            self.n_seen += k
        rest = values[k:]
        if rest.shape[0]:
            # Vectorised algorithm-R acceptance: one slot draw per element.
            # Replacement stays bounded by n_filled — after a merge leaves
            # n_filled < capacity with n_seen > n_filled, growing the sample
            # would overweight new data; replacing keeps it uniform.
            # Duplicate accepted slots: numpy fancy assignment keeps the last
            # write, matching sequential application order.
            stream_idx = self.n_seen + np.arange(rest.shape[0])
            j = self.rng.integers(0, stream_idx + 1)
            accept = j < self.n_filled
            self.buf[j[accept]] = rest[accept]
            self.n_seen += rest.shape[0]

    def sample(self) -> np.ndarray:
        return self.buf[: self.n_filled].copy()

    def state(self) -> Tuple[np.ndarray, Dict[str, object]]:
        """(retained buffer, JSON-safe metadata) for checkpointing.  The RNG
        bit-generator state rides along so post-restore acceptance draws are
        bit-identical to the never-checkpointed reservoir's."""
        meta = {"n_seen": int(self.n_seen), "n_filled": int(self.n_filled),
                "version": int(self.version),
                "rng": self.rng.bit_generator.state}
        return self.buf[: self.n_filled].copy(), meta

    def load_state(self, buf: np.ndarray, meta: Dict[str, object]) -> None:
        n_filled = int(meta["n_filled"])
        if n_filled > self.capacity or buf.shape[0] != n_filled:
            raise ValueError(f"reservoir state has {buf.shape[0]} rows for "
                             f"n_filled={n_filled}, capacity={self.capacity}")
        self.buf[:n_filled] = np.asarray(buf, np.float32)
        self.n_filled = n_filled
        self.n_seen = int(meta["n_seen"])
        self.version = int(meta["version"])
        self.rng.bit_generator.state = meta["rng"]

    def merge(self, other: "Reservoir") -> "Reservoir":
        """Weighted union: each side contributes in proportion to the stream
        size its sample represents (n_seen), not its retained-sample size —
        otherwise chained cross-host merges skew the mixture (a second-level
        merge would weight a single host as much as a pair of hosts)."""
        out = self._spawn(seed=int(self.rng.integers(1 << 31)))
        s1, s2 = self.sample(), other.sample()
        total = self.n_seen + other.n_seen
        if total == 0:
            return out
        w1 = self.n_seen / total
        w2 = other.n_seen / total
        # Cap the merged sample so the n_seen proportions are achievable from
        # the retained points: k <= len(s_i) / w_i.  Without this, a side with
        # few retained points but little stream weight would be forced in
        # wholesale and dominate the sample.
        k = min(self.capacity, len(s1) + len(s2))
        if w1 > 0:
            k = min(k, int(len(s1) / w1))
        if w2 > 0:
            k = min(k, int(len(s2) / w2))
        take1 = int(out.rng.binomial(k, w1))
        take1 = min(len(s1), max(take1, k - len(s2)))
        take2 = k - take1
        pick1 = out.rng.choice(len(s1), take1, replace=False) if take1 else []
        pick2 = out.rng.choice(len(s2), take2, replace=False) if take2 else []
        buf = np.concatenate([s1[pick1], s2[pick2]]).astype(np.float32)
        out.rng.shuffle(buf)
        out.buf[: len(buf)] = buf
        out.n_filled = len(buf)
        out.n_seen = total
        out.version = 1
        return out


class MultiReservoir(Reservoir):
    """Row-sampling reservoir over a tuple of columns.

    Keeps whole telemetry rows (column tuples) so a *joint* density can be
    fitted — per-column reservoirs sample each column independently and lose
    every cross-column correlation.  Same versioned algorithm-R acceptance
    and weighted-merge semantics as the 1-D `Reservoir`.
    """

    def __init__(self, columns: Sequence[str], capacity: int = 4096, seed: int = 0):
        self.columns = tuple(columns)
        if len(self.columns) < 2:
            raise ValueError("MultiReservoir needs >= 2 columns; use Reservoir "
                             "for a single column")
        self.backfilled = False   # seeded from per-column reservoirs (store)
        super().__init__(capacity, seed, _row_shape=(len(self.columns),))

    def _coerce(self, values: np.ndarray) -> np.ndarray:
        rows = np.asarray(values, np.float32)
        if rows.ndim != 2 or rows.shape[1] != len(self.columns):
            raise ValueError(f"expected rows of shape (m, {len(self.columns)}) "
                             f"for columns {self.columns}, got {rows.shape}")
        return rows

    def _spawn(self, seed: int) -> "MultiReservoir":
        return MultiReservoir(self.columns, self.capacity, seed=seed)

    def merge(self, other: "Reservoir") -> "Reservoir":
        if not isinstance(other, MultiReservoir) or other.columns != self.columns:
            raise ValueError(f"cannot merge joint reservoirs over different "
                             f"columns: {self.columns} vs "
                             f"{getattr(other, 'columns', None)}")
        out = super().merge(other)
        # pseudo-rows survive a merge: the flag is sticky across unions
        out.backfilled = self.backfilled or other.backfilled
        return out

    def state(self) -> Tuple[np.ndarray, Dict[str, object]]:
        buf, meta = super().state()
        meta["backfilled"] = bool(self.backfilled)
        return buf, meta

    def load_state(self, buf: np.ndarray, meta: Dict[str, object]) -> None:
        super().load_state(buf, meta)
        self.backfilled = bool(meta.get("backfilled", False))


class TieredReservoir:
    """Verdict-style tiered sample: a geometric ladder of reservoirs.

    Tier i holds `capacity >> (n_tiers-1-i)` rows, so tier 0 is 1/2^(n-1) of
    the full sample and the top tier IS the full-capacity sample.  Every
    incoming row is offered to every tier independently, so each tier is a
    uniform sample of the whole stream on its own — a query answered from
    tier 0 is a cheap, coarse, *unbiased* answer, and progressive execution
    re-answers on successively larger tiers until the top tier reproduces
    the untiered result bit-for-bit.  Members share the versioned algorithm-R
    acceptance and weighted-merge core of `Reservoir`/`MultiReservoir`.

    Optional per-dictionary-code stratification (`strat_column`): a small
    side reservoir per distinct code of one column, so rare GROUP BY groups
    whose representatives would be displaced from the uniform tiers keep
    coverage.  Strata feed group *discovery* and worst-case retention
    (`codes()`/`stratum()`); aggregate estimates still come from the uniform
    tiers, which keeps them unbiased.

    `columns=None` samples scalars (1-D column); a tuple samples whole rows
    like `MultiReservoir`.  `version`/`n_seen`/`n_filled` delegate to the
    top tier, so synopsis caches and admission re-keying work unchanged.
    """

    backfilled = False   # tiered joints are never seeded from marginals

    def __init__(self, capacity: int = 4096, n_tiers: int = 4, seed: int = 0,
                 columns: Optional[Sequence[str]] = None,
                 strat_column: Optional[str] = None,
                 strata_capacity: int = 64, max_strata: int = 256):
        if n_tiers < 1:
            raise ValueError(f"n_tiers must be >= 1, got {n_tiers}")
        if capacity >> (n_tiers - 1) < 1:
            raise ValueError(f"capacity {capacity} too small for {n_tiers} "
                             f"tiers (tier 0 would be empty)")
        self.capacity = capacity
        self.n_tiers = n_tiers
        self.seed = seed
        self.columns = tuple(columns) if columns is not None else None
        self.strat_column = strat_column
        self.strata_capacity = strata_capacity
        self.max_strata = max_strata
        self._strat_axis: Optional[int] = None
        if strat_column is not None and self.columns is not None:
            if strat_column not in self.columns:
                raise ValueError(f"strat_column {strat_column!r} not in "
                                 f"columns {self.columns}")
            self._strat_axis = self.columns.index(strat_column)
        self.tiers = [self._spawn_member(capacity >> (n_tiers - 1 - i),
                                         seed + i)
                      for i in range(n_tiers)]
        self.strata: Dict[float, Reservoir] = {}
        self.strata_overflow = False

    def _spawn_member(self, cap: int, seed: int) -> Reservoir:
        if self.columns is None:
            return Reservoir(cap, seed=seed)
        return MultiReservoir(self.columns, cap, seed=seed)

    # synopsis caching / admission re-keying key on these; the top tier is
    # the authoritative (full) sample, so its counters speak for the whole
    @property
    def version(self) -> int:
        return self.tiers[-1].version

    @property
    def n_seen(self) -> int:
        return self.tiers[-1].n_seen

    @property
    def n_filled(self) -> int:
        return self.tiers[-1].n_filled

    def _stratum_seed(self, code: float) -> int:
        return (self.seed + 7919
                + zlib.crc32(np.float32(code).tobytes()) % 100003)

    def add(self, values: np.ndarray) -> None:
        values = self.tiers[-1]._coerce(np.asarray(values, np.float32))
        if values.shape[0] == 0:
            return
        for tier in self.tiers[:-1]:
            tier.add(values)
        if self.strat_column is not None:
            codes = values if self._strat_axis is None \
                else values[:, self._strat_axis]
            for code in np.unique(codes):
                if np.isnan(code):
                    continue
                key = float(code)
                res = self.strata.get(key)
                if res is None:
                    if len(self.strata) >= self.max_strata:
                        # stop opening NEW strata; existing ones keep updating
                        self.strata_overflow = True
                        continue
                    res = self._spawn_member(self.strata_capacity,
                                             self._stratum_seed(key))
                    self.strata[key] = res
                res.add(values[codes == code])
        self.tiers[-1].add(values)

    def sample(self, tier: Optional[int] = None) -> np.ndarray:
        """The retained sample of one tier (default: the full top tier)."""
        if tier is None:
            return self.tiers[-1].sample()
        tier = max(0, min(int(tier), self.n_tiers - 1))
        return self.tiers[tier].sample()

    def tier_sizes(self) -> List[int]:
        return [t.n_filled for t in self.tiers]

    def codes(self) -> List[float]:
        """Distinct stratification codes seen so far (sorted) — the GROUP BY
        discovery set; unions with the uniform sample's codes so rare groups
        displaced from the tiers still get result rows."""
        return sorted(self.strata)

    def stratum(self, code: float) -> Optional[np.ndarray]:
        res = self.strata.get(float(np.float32(code)))
        return None if res is None else res.sample()

    def merge(self, other: "TieredReservoir") -> "TieredReservoir":
        if not isinstance(other, TieredReservoir) \
                or other.n_tiers != self.n_tiers \
                or other.columns != self.columns \
                or other.strat_column != self.strat_column:
            raise ValueError(
                f"cannot merge tiered reservoirs with different shape: "
                f"{(self.n_tiers, self.columns, self.strat_column)} vs "
                f"{(getattr(other, 'n_tiers', None), getattr(other, 'columns', None), getattr(other, 'strat_column', None))}")
        out = TieredReservoir(
            self.capacity, self.n_tiers,
            seed=int(self.tiers[-1].rng.integers(1 << 31)),
            columns=self.columns, strat_column=self.strat_column,
            strata_capacity=self.strata_capacity,
            max_strata=self.max_strata)
        out.tiers = [a.merge(b) for a, b in zip(self.tiers, other.tiers)]
        for key in set(self.strata) | set(other.strata):
            a, b = self.strata.get(key), other.strata.get(key)
            out.strata[key] = a.merge(b) if a is not None and b is not None \
                else copy.deepcopy(a if a is not None else b)
        out.strata_overflow = self.strata_overflow or other.strata_overflow
        return out

    def state(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        """(arrays, JSON-safe metadata) for checkpointing — every tier and
        stratum rides along with its RNG state, so a restored ladder accepts
        future rows bit-identically to the never-checkpointed one."""
        arrays: Dict[str, np.ndarray] = {}
        tier_meta = []
        for i, tier in enumerate(self.tiers):
            buf, m = tier.state()
            arrays[f"tier{i}/buf"] = buf
            tier_meta.append(m)
        strata_meta = []
        for j, code in enumerate(sorted(self.strata)):
            buf, m = self.strata[code].state()
            arrays[f"strata/{j}/buf"] = buf
            strata_meta.append({"code": float(code), "meta": m})
        meta = {"kind": "tiered", "n_tiers": int(self.n_tiers),
                "capacity": int(self.capacity), "seed": int(self.seed),
                "columns": list(self.columns) if self.columns else None,
                "strat_column": self.strat_column,
                "strata_capacity": int(self.strata_capacity),
                "max_strata": int(self.max_strata),
                "strata_overflow": bool(self.strata_overflow),
                "tiers": tier_meta, "strata": strata_meta}
        return arrays, meta

    @classmethod
    def from_state(cls, arrays: Dict[str, np.ndarray],
                   meta: Dict[str, object]) -> "TieredReservoir":
        cols = meta.get("columns")
        out = cls(capacity=int(meta["capacity"]),
                  n_tiers=int(meta["n_tiers"]), seed=int(meta["seed"]),
                  columns=tuple(cols) if cols else None,
                  strat_column=meta.get("strat_column"),
                  strata_capacity=int(meta["strata_capacity"]),
                  max_strata=int(meta["max_strata"]))
        for i, m in enumerate(meta["tiers"]):
            out.tiers[i].load_state(arrays[f"tier{i}/buf"], m)
        for j, ent in enumerate(meta["strata"]):
            code = float(ent["code"])
            res = out._spawn_member(out.strata_capacity,
                                    out._stratum_seed(code))
            res.load_state(arrays[f"strata/{j}/buf"], ent["meta"])
            out.strata[code] = res
        out.strata_overflow = bool(meta.get("strata_overflow", False))
        return out


class CategoricalSketch:
    """Exact per-code frequency sketch for a dictionary column.

    Dictionary columns hold a small set of unit-spaced codes, so keeping ONE
    counter per code alongside the reservoir answers Eq-term aggregates
    *exactly* — no kernel smoothing, no sample->relation scaling.  The KDE
    code±1/2 window stays as the fallback for untracked columns (and for
    sketches that do not cover the column's whole stream).

    `n_rows` counts every value the sketch has seen; the engine only takes
    the exact path when it equals the reservoir's `n_seen` (i.e. the sketch
    was registered before any data and never missed a batch).  A column
    whose distinct-code count exceeds `max_codes` is not dictionary-like;
    the sketch marks itself `overflowed` and the exact path disables itself
    (for high-cardinality columns, `CountMinSketch` degrades to bounded-error
    counts instead).
    """

    path = "exact"    # AqpResult.path label when this sketch answers

    def __init__(self, max_codes: int = 4096):
        self.counts: Dict[float, int] = {}
        self.n_rows = 0
        self.max_codes = max_codes
        self.overflowed = False

    def add(self, values: np.ndarray) -> None:
        # float32, matching Reservoir._coerce: a code that is not exactly
        # float32-representable must count under the SAME rounded code on the
        # exact path and in the KDE sample, or the two paths disagree
        values = np.asarray(values, np.float32).ravel()
        if values.shape[0] == 0:
            return
        if not self.overflowed:
            codes, counts = np.unique(values, return_counts=True)
            for c, k in zip(codes, counts):
                self.counts[float(c)] = self.counts.get(float(c), 0) + int(k)
            if len(self.counts) > self.max_codes:
                self.overflowed = True
                self.counts.clear()
        # n_rows LAST: the store bumps the reservoir's n_seen before this
        # add runs, so a concurrent reader mid-update sees n_rows < n_seen
        # and `exact_for` conservatively routes it to the KDE fallback
        # rather than serving half-updated counts as "exact"
        self.n_rows += values.shape[0]

    def exact_for(self, n_seen: int) -> bool:
        """True when the sketch covers the column's entire stream."""
        return not self.overflowed and self.n_rows == n_seen

    def range_terms(self, lo: float, hi: float) -> Tuple[int, float]:
        """(COUNT, SUM of code values) over codes in [lo, hi] — exact."""
        cnt = 0
        sm = 0.0
        # snapshot: a concurrent add() may insert codes mid-iteration
        for code, k in list(self.counts.items()):
            if lo <= code <= hi:
                cnt += k
                sm += code * k
        return cnt, sm

    def merge(self, other: "CategoricalSketch") -> "CategoricalSketch":
        out = CategoricalSketch(max_codes=min(self.max_codes, other.max_codes))
        out.n_rows = self.n_rows + other.n_rows
        out.overflowed = self.overflowed or other.overflowed
        if not out.overflowed:
            out.counts = dict(self.counts)
            for c, k in other.counts.items():
                out.counts[c] = out.counts.get(c, 0) + k
            if len(out.counts) > out.max_codes:
                out.overflowed = True
                out.counts.clear()
        return out

    def stats(self) -> Dict[str, object]:
        return {"kind": "exact", "codes": len(self.counts),
                "rows": self.n_rows, "overflowed": self.overflowed}

    def state(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        """(arrays, JSON-safe metadata) for checkpointing."""
        # iterate items() rather than re-deriving keys from a float32 array:
        # NaN codes are legal dict keys here but can never be looked up
        # again (nan != nan), so a rebuilt-key path would KeyError
        items = list(self.counts.items())
        codes = np.asarray([c for c, _ in items], np.float32)
        counts = np.asarray([k for _, k in items], np.int64)
        meta = {"kind": "exact", "n_rows": int(self.n_rows),
                "max_codes": int(self.max_codes),
                "overflowed": bool(self.overflowed)}
        return {"codes": codes, "counts": counts}, meta

    @classmethod
    def from_state(cls, arrays: Dict[str, np.ndarray],
                   meta: Dict[str, object]) -> "CategoricalSketch":
        out = cls(max_codes=int(meta["max_codes"]))
        out.n_rows = int(meta["n_rows"])
        out.overflowed = bool(meta["overflowed"])
        out.counts = {float(c): int(k) for c, k
                      in zip(arrays["codes"], arrays["counts"])}
        return out


_CM_MAX = (1 << 32) - 1      # uint32 saturation cap for CountMinSketch cells


class CountMinSketch:
    """Bounded-error per-code counts for high-cardinality dictionary columns.

    `CategoricalSketch` is all-or-nothing: past `max_codes` distinct codes it
    overflows and every Eq query falls back to KDE smoothing.  A count-min
    sketch (Cormode & Muthukrishnan; cf. the hashing-based estimators of
    Charikar & Siminelakis) never overflows: each value increments one cell
    per row of a (depth x width) counter table through independent
    multiply-shift hashes, and a code's estimated count is the MIN over its
    depth cells.  Estimates only over-count (hash collisions add, never
    subtract): with probability >= 1 - exp(-depth) the error is at most
    (e / width) * n_rows.  Registered via `track_categorical(col, kind="cm")`
    and reported on path "exact:cm" — same coverage gate as the exact sketch
    (the sketch must have seen the whole stream), bounded error instead of
    none.

    `conservative=True` (Estan & Varghese conservative update) raises a
    code's cells only as far as needed: per distinct code in a batch,
    `cells = max(cells, estimate(code) + batch_count)`.  Every cell stays an
    upper bound for every code hashing into it (the estimate >= the code's
    true pre-batch count by induction, so estimate + batch_count >= its new
    true count, and no other cell decreases), so the min-estimate still
    never under-counts — but cells stop absorbing the full collision mass,
    which cuts realised error well below the standard update on skewed
    streams (test-enforced).  The analytic `err_bound` is unchanged (a
    worst-case bound either way).  Merging adds tables cell-wise as before —
    the per-sketch upper-bound invariant is additive — but the merged sketch
    is only flagged conservative when both inputs are.

    Counters are packed to uint32 (half the checkpoint bytes of the original
    int64 table) with SATURATING adds: a cell that would pass 2^32 - 1 clips
    there and bumps `saturated`.  A clipped cell stops being an upper bound
    for the codes hashing into it — the min-estimate could then under-count —
    so any saturation drops the coverage gate (`exact_for` returns False) and
    the engine falls back to KDE smoothing instead of serving a broken bound
    on an "exact:cm" label.  Reaching the cap takes 4 billion rows into one
    cell; the counter exists so that if it ever happens the failure is a
    visible path change, not silent wraparound.  Legacy int64 snapshots load
    unchanged (values above the cap clip and count as saturations).

    Code grid: a count-min table cannot enumerate its keys, so range
    answers walk an assumed code lattice `grid_origin + k * grid_step`
    (default: the integers).  Before this was explicit, a column whose
    dictionary codes sit off the integer lattice (half codes, scaled ids)
    answered range queries from the WRONG enumeration — COUNT missed every
    off-lattice code and SUM mis-weighted what it did hit, silently, on a
    path labelled "exact:cm".  Now the sketch verifies each batch against
    its declared grid: any off-grid value flips `off_grid` and range
    answers return None forever after (point `estimate` stays valid), so
    the engine falls back to the KDE instead of serving a wrong exact
    answer.  Declaring the true grid (`track_categorical(...,
    grid_step=0.5)`) restores exact-path coverage with correctly weighted
    sums (regression-tested).
    """

    path = "exact:cm"

    def __init__(self, width: int = 2048, depth: int = 4, seed: int = 0,
                 max_enumerate: int = 64, conservative: bool = False,
                 grid_step: float = 1.0, grid_origin: float = 0.0):
        if width < 1 or depth < 1:
            raise ValueError(f"width/depth must be >= 1, got {width}x{depth}")
        if not grid_step > 0:
            raise ValueError(f"grid_step must be > 0, got {grid_step}")
        self.width = width
        self.depth = depth
        self.seed = seed
        self.conservative = conservative
        self.max_enumerate = max_enumerate   # widest code window enumerated
        self.grid_step = float(grid_step)
        self.grid_origin = float(grid_origin)
        self.off_grid = False                # any value seen off the lattice
        self.table = np.zeros((depth, width), np.uint32)
        self.saturated = 0                   # cumulative cell-clip events
        self.n_rows = 0
        self.overflowed = False              # a CM sketch never overflows
        rng = np.random.default_rng(seed)
        # odd multipliers for 64-bit multiply-shift hashing of the code's
        # float32 bit pattern; deterministic in `seed` so merges line up
        self._mul = (rng.integers(1, 1 << 61, size=depth, dtype=np.uint64)
                     * np.uint64(2) + np.uint64(1))
        self._add = rng.integers(0, 1 << 61, size=depth, dtype=np.uint64)

    def _hash(self, codes: np.ndarray, row: int) -> np.ndarray:
        bits = np.asarray(codes, np.float32).view(np.uint32).astype(np.uint64)
        mixed = (self._mul[row] * bits + self._add[row]) >> np.uint64(33)
        return (mixed % np.uint64(self.width)).astype(np.int64)

    def add(self, values: np.ndarray) -> None:
        # float32 like Reservoir._coerce / CategoricalSketch.add: both paths
        # must bucket a non-representable code under the same rounded value
        values = np.asarray(values, np.float32).ravel()
        if values.shape[0] == 0:
            return
        if not self.off_grid:
            # snap each value to the declared lattice and compare the float32
            # bit patterns: a mismatch means the column's codes are not where
            # range enumeration will look for them, so disable range answers
            # (cell counts and point estimates stay valid)
            k = np.rint((values.astype(np.float64) - self.grid_origin)
                        / self.grid_step)
            snapped = np.asarray(self.grid_origin + k * self.grid_step,
                                 np.float32)
            if not np.array_equal(snapped.view(np.uint32),
                                  values.view(np.uint32)):
                self.off_grid = True
        if self.conservative:
            # conservative update, vectorised per distinct code: read every
            # code's current min-estimate against the pre-batch table, then
            # raise its cells to at most estimate + batch count.  Reading all
            # estimates before any write only makes estimates lower (tighter)
            # than the sequential formulation — the upper-bound invariant
            # needs estimate >= the code's own pre-batch count, which the
            # pre-batch table already guarantees.
            codes, counts = np.unique(values, return_counts=True)
            idx = np.stack([self._hash(codes, r) for r in range(self.depth)])
            cur = np.stack([self.table[r, idx[r]]
                            for r in range(self.depth)])
            target = cur.astype(np.int64).min(axis=0) + counts
            over = target > _CM_MAX
            if over.any():                   # saturate, don't wrap
                self.saturated += int(over.sum())
                target = np.minimum(target, _CM_MAX)
            target = target.astype(np.uint32)
            for r in range(self.depth):
                np.maximum.at(self.table[r], idx[r], target)
        else:
            # widen to int64 for the add (np.add.at on uint32 would wrap
            # silently), then clip back into the packed cells
            for r in range(self.depth):
                inc = np.bincount(self._hash(values, r),
                                  minlength=self.width)
                new = self.table[r].astype(np.int64) + inc
                over = new > _CM_MAX
                if over.any():
                    self.saturated += int(over.sum())
                    new = np.minimum(new, _CM_MAX)
                self.table[r] = new.astype(np.uint32)
        # n_rows last, same reason as CategoricalSketch.add: a concurrent
        # reader mid-update must see n_rows < n_seen and fall back
        self.n_rows += values.shape[0]

    def estimate(self, code: float) -> int:
        """Estimated count of one code: min over the depth cells (>= truth)."""
        idx = [self._hash(np.asarray([code], np.float32), r)[0]
               for r in range(self.depth)]
        return int(min(self.table[r, i] for r, i in zip(range(self.depth), idx)))

    def exact_for(self, n_seen: int) -> bool:
        """Coverage gate, same contract as `CategoricalSketch.exact_for`:
        True when the sketch has seen the column's entire stream.  Covered
        answers are bounded-error (err <= e/width * n_rows w.h.p.), not
        exact — the engine labels them "exact:cm".  A saturated cell has
        dropped mass and may UNDER-count, voiding the error bound, so any
        saturation drops coverage and routes queries back to the KDE."""
        return self.n_rows == n_seen and self.saturated == 0

    def _grid_codes(self, lo: float, hi: float) -> Optional[List[float]]:
        """Deduplicated float32 lattice codes inside [lo, hi], or None when
        the window spans more than `max_enumerate` grid points.  The small
        epsilon absorbs float64 division fuzz so a query bound sitting ON a
        grid point always includes it."""
        step, origin = self.grid_step, self.grid_origin
        first = int(np.ceil((lo - origin) / step - 1e-9))
        last = int(np.floor((hi - origin) / step + 1e-9))
        if last < first:
            return []
        if last - first + 1 > self.max_enumerate:
            return None
        out: List[float] = []
        seen = set()
        for k in range(first, last + 1):
            # grid points beyond float32 resolution can alias to one code;
            # count the shared cell once
            code32 = float(np.float32(origin + k * step))
            if code32 not in seen:
                seen.add(code32)
                out.append(code32)
        return out

    def range_terms(self, lo: float, hi: float) -> Optional[Tuple[int, float]]:
        """(COUNT, SUM of code values) over lattice codes in [lo, hi], each
        code's count weighted by its actual (possibly fractional) value.
        None when the window spans more than `max_enumerate` grid points (a
        count-min sketch cannot enumerate its keys, so wide windows go back
        to the KDE path rather than summing unbounded collision noise) or
        when the stream has produced off-grid values — the enumeration would
        miss them, so the KDE path answers instead."""
        if self.off_grid:
            return None
        codes = self._grid_codes(lo, hi)
        if codes is None:
            return None
        cnt = 0
        sm = 0.0
        for code32 in codes:
            k = self.estimate(code32)
            cnt += k
            sm += code32 * k
        return cnt, sm

    def err_bound(self) -> int:
        """Counts overshoot by at most this many rows, w.p. >= 1-exp(-depth)."""
        return int(np.ceil(np.e / self.width * self.n_rows))

    def range_err(self, lo: float, hi: float
                  ) -> Optional[Tuple[int, float, float]]:
        """Worst-case over-count mass for a `range_terms(lo, hi)` answer:
        (count error, positive sum error, negative sum error), or None when
        the window is too wide to enumerate or the stream went off-grid.
        Count-min only over-counts, so COUNT truth lies in
        [est - count_err, est] and SUM truth in
        [est - sum_pos_err, est + sum_neg_err] (over-counted negative codes
        push the estimated sum DOWN, so truth can sit above it)."""
        if self.off_grid:
            return None
        codes = self._grid_codes(lo, hi)
        if codes is None:
            return None
        eb = self.err_bound()
        cnt_err = 0
        sum_pos = 0.0
        sum_neg = 0.0
        for code32 in codes:
            cnt_err += eb
            if code32 >= 0:
                sum_pos += eb * code32
            else:
                sum_neg += eb * (-code32)
        return cnt_err, sum_pos, sum_neg

    def merge(self, other: "CountMinSketch") -> "CountMinSketch":
        # compare the actual hash parameters, not just the seed: a sketch
        # restored from a snapshot keeps its persisted multipliers even if
        # the local numpy derives different ones from the same seed
        if (self.width, self.depth) != (other.width, other.depth) \
                or not np.array_equal(self._mul, other._mul) \
                or not np.array_equal(self._add, other._add):
            raise ValueError(
                f"cannot merge count-min sketches with different geometry: "
                f"{(self.width, self.depth, self.seed)} vs "
                f"{(other.width, other.depth, other.seed)} "
                f"(or unequal hash parameters)")
        if (self.grid_step, self.grid_origin) != (other.grid_step,
                                                  other.grid_origin):
            raise ValueError(
                f"cannot merge count-min sketches over different code grids: "
                f"step/origin {(self.grid_step, self.grid_origin)} vs "
                f"{(other.grid_step, other.grid_origin)}")
        out = CountMinSketch(self.width, self.depth, self.seed,
                             max_enumerate=min(self.max_enumerate,
                                               other.max_enumerate),
                             conservative=self.conservative
                             and other.conservative,
                             grid_step=self.grid_step,
                             grid_origin=self.grid_origin)
        out._mul = self._mul.copy()
        out._add = self._add.copy()
        summed = self.table.astype(np.int64) + other.table.astype(np.int64)
        over = summed > _CM_MAX
        out.saturated = self.saturated + other.saturated + int(over.sum())
        if over.any():
            summed = np.minimum(summed, _CM_MAX)
        out.table = summed.astype(np.uint32)
        out.n_rows = self.n_rows + other.n_rows
        out.off_grid = self.off_grid or other.off_grid
        return out

    def stats(self) -> Dict[str, object]:
        return {"kind": "cm", "rows": self.n_rows, "overflowed": False,
                "width": self.width, "depth": self.depth,
                "conservative": self.conservative,
                "grid_step": self.grid_step, "grid_origin": self.grid_origin,
                "off_grid": self.off_grid, "saturated": self.saturated,
                "err_bound": self.err_bound()}

    def state(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        meta = {"kind": "cm", "n_rows": int(self.n_rows),
                "width": int(self.width), "depth": int(self.depth),
                "seed": int(self.seed),
                "conservative": bool(self.conservative),
                "grid_step": float(self.grid_step),
                "grid_origin": float(self.grid_origin),
                "off_grid": bool(self.off_grid),
                "saturated": int(self.saturated),
                "max_enumerate": int(self.max_enumerate)}
        # the hash multipliers are persisted, not re-derived on load: numpy
        # does not guarantee Generator streams across versions, and a table
        # read through different hashes is silently wrong
        return {"table": self.table.copy(), "mul": self._mul.copy(),
                "add": self._add.copy()}, meta

    @classmethod
    def from_state(cls, arrays: Dict[str, np.ndarray],
                   meta: Dict[str, object]) -> "CountMinSketch":
        # `conservative`/grid defaults: pre-flag snapshots load as standard
        # sketches on the integer lattice (exactly what they assumed)
        out = cls(int(meta["width"]), int(meta["depth"]), int(meta["seed"]),
                  max_enumerate=int(meta["max_enumerate"]),
                  conservative=bool(meta.get("conservative", False)),
                  grid_step=float(meta.get("grid_step", 1.0)),
                  grid_origin=float(meta.get("grid_origin", 0.0)))
        out.off_grid = bool(meta.get("off_grid", False))
        out._mul = np.asarray(arrays["mul"], np.uint64)
        out._add = np.asarray(arrays["add"], np.uint64)
        out.saturated = int(meta.get("saturated", 0))
        # legacy snapshots persisted int64 tables; values past the uint32
        # cap clip on load and register as saturations so the coverage gate
        # sees the (theoretical) broken bound rather than a wrapped cell
        raw = np.asarray(arrays["table"], np.int64).reshape(
            out.depth, out.width)
        over = raw > _CM_MAX
        if over.any():
            out.saturated += int(over.sum())
            raw = np.minimum(raw, _CM_MAX)
        out.table = raw.astype(np.uint32)
        out.n_rows = int(meta["n_rows"])
        return out


_SKETCH_KINDS = {"exact": CategoricalSketch, "cm": CountMinSketch}


def _entry_nbytes(syn) -> int:
    """Byte footprint of a cached synopsis — the device payload (sample +
    bandwidth).  `repro.synopses` backends report their own `nbytes` (an RFF
    synopsis carries no sample, only its (W, b, z) triple); legacy
    `KDESynopsis` payloads are sized from their arrays.  Payloads without
    device arrays size to 0; the entry bound still applies to them."""
    own = getattr(syn, "nbytes", None)
    if isinstance(own, int):
        return own
    nb = 0
    for attr in ("x", "h", "H"):
        v = getattr(syn, attr, None)
        if v is not None and hasattr(v, "nbytes"):
            nb += int(v.nbytes)
    return nb


class SynopsisCache:
    """Memoises fitted synopses keyed by (column-or-tuple, selector, version).

    One live entry per (column, selector): a lookup whose stored version
    differs from the reservoir's current version is a miss and is replaced on
    the next `put` — reservoir updates therefore invalidate implicitly.
    Bounded by `max_entries` and (optionally) `max_bytes`, with LRU eviction:
    hits refresh recency, eviction pops the least-recently-used entry and is
    counted in `stats()`.

    Thread-safe: concurrent query threads hit `get`/`put` (every hit mutates
    LRU order) while serving, and a snapshot (`entries`, via
    `TelemetryStore.to_state`) must see a consistent entry list — all
    internal state is guarded by one lock.
    """

    def __init__(self, max_entries: int = 128, max_bytes: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        # (column-or-tuple, selector) -> (version, synopsis, nbytes)
        self._entries: OrderedDict = OrderedDict()  # guarded-by: _lock
        self.hits = 0          # guarded-by: _lock
        self.misses = 0        # guarded-by: _lock
        self.evictions = 0     # guarded-by: _lock
        self.oversize = 0      # guarded-by: _lock
        self._bytes = 0        # guarded-by: _lock
        self._lock = threading.Lock()
        # registry mirror (always-on when a registry is supplied — one lock +
        # add per event): instruments resolved once here, not per lookup
        if metrics is not None:
            self._m_hits = metrics.counter("aqp.cache.hits")
            self._m_misses = metrics.counter("aqp.cache.misses")
            self._m_evictions = metrics.counter("aqp.cache.evictions")
            self._m_entries = metrics.gauge("aqp.cache.entries")
            self._m_bytes = metrics.gauge("aqp.cache.bytes")
        else:
            self._m_hits = self._m_misses = self._m_evictions = None
            self._m_entries = self._m_bytes = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def get(self, column: ColumnKey, selector: str, version: int) -> Optional[KDESynopsis]:
        return self.lookup(column, selector, version)[0]

    def lookup(self, column: ColumnKey, selector: str,
               version: int) -> Tuple[Optional[KDESynopsis], bool]:
        """`get`, and on a miss whether the entry was held at an older
        version (an insert left it stale): (synopsis or None, stale)."""
        # selector case-normalized: "Plugin" and "plugin" are the same
        # synopsis and must share one entry, not collide as two live copies
        key = (column, canonical_selector(selector))
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None and ent[0] == version:
                self.hits += 1
                if self._m_hits is not None:
                    self._m_hits.inc()
                self._entries.move_to_end(key)        # LRU: refresh recency
                return ent[1], False
            self.misses += 1
            if self._m_misses is not None:
                self._m_misses.inc()
            return None, ent is not None and ent[0] < version

    def put(self, column: ColumnKey, selector: str, version: int, syn: KDESynopsis) -> None:
        key = (column, canonical_selector(selector))
        nb = _entry_nbytes(syn)
        with self._lock:
            if self.max_bytes is not None and nb > self.max_bytes:
                # An entry that can never fit must not flush the whole cache
                # on its way through the eviction loop; refuse it and keep
                # the rest.
                self.oversize += 1
                if key in self._entries:
                    self._bytes -= self._entries.pop(key)[2]
                return
            if key in self._entries:
                self._bytes -= self._entries.pop(key)[2]
            self._entries[key] = (version, syn, nb)
            self._bytes += nb
            while (len(self._entries) > self.max_entries
                   or (self.max_bytes is not None
                       and self._bytes > self.max_bytes)):
                _, (_, _, ev_nb) = self._entries.popitem(last=False)
                self._bytes -= ev_nb
                self.evictions += 1
                if self._m_evictions is not None:
                    self._m_evictions.inc()
            if self._m_entries is not None:
                self._m_entries.set(len(self._entries))
                self._m_bytes.set(self._bytes)

    def peek(self, column: ColumnKey, selector: str,
             version: int) -> Optional[KDESynopsis]:
        """Non-counting `get`: no hit/miss counters, no LRU refresh.  The
        admission fit-offload guard uses this to ask "is the fit already
        done?" without skewing cache statistics or recency."""
        key = (column, canonical_selector(selector))
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None and ent[0] == version:
                return ent[1]
            return None

    def invalidate(self, column: Optional[ColumnKey] = None) -> None:
        with self._lock:
            if column is None:
                self._entries.clear()
                self._bytes = 0
                return
            for key in [k for k in self._entries if k[0] == column]:
                self._bytes -= self._entries.pop(key)[2]

    def entries(self) -> List[Tuple[Tuple[Hashable, str], int, KDESynopsis]]:
        """Consistent snapshot of the live entries, LRU order:
        [(key, version, synopsis)] — the durable-state serializer's view."""
        with self._lock:
            return [(key, version, syn) for key, (version, syn, _nb)
                    in self._entries.items()]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries), "bytes": self._bytes,
                    "evictions": self.evictions, "oversize": self.oversize}


class TelemetryStore:
    def __init__(self, capacity: int = 4096, seed: int = 0,
                 cache_entries: int = 128, cache_bytes: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None):
        # the three registries allow unlocked reads by design (query paths
        # tolerate a stale view; reservoirs are internally consistent), but
        # every *mutation* must hold _write_lock so snapshots (to_state) and
        # concurrent track_*/add_batch calls cannot interleave
        self.columns: Dict[str, Reservoir] = {}         # guarded-by: _write_lock (writes)
        self.joints: Dict[Tuple[str, ...], MultiReservoir] = {}  # guarded-by: _write_lock (writes)
        self.categoricals: Dict[str, CategoricalSketch] = {}  # guarded-by: _write_lock (writes)
        self.capacity = capacity
        self.seed = seed
        # every store owns a MetricsRegistry (or shares an injected one):
        # engine/admission/cache instruments all land here, so co-hosted
        # stores and tests stay isolated while `serve --metrics-out` exports
        # one store's registry plus the process-global kernel registry
        self.metrics = metrics if metrics is not None else obs.MetricsRegistry()
        self.cache = SynopsisCache(max_entries=cache_entries,
                                   max_bytes=cache_bytes,
                                   metrics=self.metrics)
        self._listeners: List[Callable[[Dict[ColumnKey, int]], None]] = []  # guarded-by: _write_lock
        self._sessions: List["weakref.ref"] = []        # guarded-by: _write_lock
        # shared engines keyed (selector, backend): query()/session() route
        # through these so PlanCache entries persist across calls and can be
        # checkpointed/restored (warm starts skip replanning)
        self._engines: Dict[Tuple[str, str], object] = {}  # guarded-by: _write_lock (writes)
        # serializes mutation (add_batch/restore_state) against snapshots
        # (to_state): a snapshot taken mid-add_batch could otherwise persist
        # a sketch whose n_rows exceeds its reservoir's n_seen — a restored
        # store would then claim exact coverage it does not have
        self._write_lock = threading.RLock()

    def _col_seed(self, name: str) -> int:
        # crc32, not hash(): Python string hashing is randomised per
        # process, which would make the reservoirs nondeterministic.
        return self.seed + zlib.crc32(name.encode()) % 1000

    def track_joint(self, columns: Sequence[str], backfill: bool = True) -> None:
        """Register a joint (row) reservoir over a column tuple.

        Only rows arriving *after* registration are sampled exactly.  When the
        columns are already tracked per-column, the joint reservoir is seeded
        by replaying the per-column reservoirs' current samples zip-aligned
        (a window of pseudo-rows): the marginals are right immediately, but
        cross-column correlation only accumulates as real rows stream in.
        The seed is flagged as `backfilled` in `stats()`; pass
        `backfill=False` to start empty instead.
        """
        key = tuple(columns)
        # registration is a write: hold _write_lock for the whole
        # check-backfill-insert sequence so a concurrent add_batch cannot
        # advance the per-column reservoirs between the backfill read and
        # the joint's n_seen stamp (a torn backfill would under-count)
        with self._write_lock:
            if key in self.joints:
                return
            res = MultiReservoir(key, self.capacity,
                                 seed=self._col_seed("|".join(key)))
            if backfill and all(c in self.columns
                                and self.columns[c].n_filled > 0
                                for c in key):
                samples = [self.columns[c].sample() for c in key]
                k = min(s.shape[0] for s in samples)  # zip-aligned window
                res.add(np.stack([s[:k] for s in samples], axis=1))
                # The window stands in for the paired stream the per-column
                # reservoirs summarize, so the joint's stream size is theirs
                # — not k.  Without this, sample->relation scaling (and
                # weighted merges) would treat the backfill as a k-row
                # relation.
                res.n_seen = min(self.columns[c].n_seen for c in key)
                res.backfilled = True
            self.joints[key] = res

    def track_tiered(self, columns: ColumnKey, n_tiers: int = 4,
                     strat_column: Optional[str] = None,
                     strata_capacity: int = 64,
                     max_strata: int = 256) -> None:
        """Upgrade a column (str) or joint tuple to a `TieredReservoir` so
        queries can trade accuracy for latency: tier 0 answers from a
        1/2^(n_tiers-1) sample, progressive mode refines tier by tier, and
        the top tier reproduces untiered answers bit-for-bit.  Register
        *before* the first `add_batch` — an existing reservoir with data
        cannot be converted (its stream is gone).  `strat_column` keeps a
        small per-code side sample for rare GROUP BY groups."""
        if isinstance(columns, str):
            name: ColumnKey = columns
            registry: Dict = self.columns
            seed = self._col_seed(columns)
            if strat_column is not None and strat_column != columns:
                raise ValueError(f"strat_column {strat_column!r} must equal "
                                 f"the tracked column {columns!r} for 1-D "
                                 f"tiered reservoirs")
            member_cols = None
            strat = columns if strat_column is not None else None
        else:
            name = tuple(columns)
            registry = self.joints
            seed = self._col_seed("|".join(name))
            member_cols = name
            strat = strat_column
        # `registry` aliases self.columns / self.joints: the insert below is
        # a store mutation and must not interleave with add_batch's
        # create-if-missing for the same name
        with self._write_lock:
            existing = registry.get(name)
            if isinstance(existing, TieredReservoir):
                return
            if existing is not None and existing.n_seen > 0:
                raise ValueError(f"cannot convert reservoir {name!r} with "
                                 f"{existing.n_seen} rows seen to tiered; "
                                 f"call track_tiered before add_batch")
            registry[name] = TieredReservoir(
                self.capacity, n_tiers=n_tiers, seed=seed,
                columns=member_cols, strat_column=strat,
                strata_capacity=strata_capacity, max_strata=max_strata)

    def track_categorical(self, column: str, max_codes: int = 4096,
                          kind: str = "exact", width: int = 2048,
                          depth: int = 4, conservative: bool = False,
                          grid_step: float = 1.0,
                          grid_origin: float = 0.0) -> None:
        """Register a per-code frequency sketch for a dictionary column.
        Register *before* the column's first `add_batch` — the engine's
        exact Eq path requires the sketch to cover the whole stream
        (otherwise it falls back to the KDE code-window estimate; see
        `stats()["categoricals"]` for coverage).

        kind="exact" (default) keeps one exact counter per code but disables
        itself past `max_codes` distinct codes; kind="cm" keeps a
        (depth x width) count-min table instead — bounded-error counts
        (path "exact:cm") for columns too wide to enumerate.
        `conservative=True` (kind="cm" only) switches the table to
        conservative updates: same worst-case bound, much lower realised
        error on skewed streams (see `CountMinSketch`).
        `grid_step`/`grid_origin` (kind="cm" only) declare the column's code
        lattice for range enumeration — codes observed off the declared grid
        disable range answers rather than mis-weighting them (see
        `CountMinSketch`); the exact sketch keys codes directly and needs no
        grid."""
        with self._write_lock:
            if column in self.categoricals:
                return
            if kind == "exact":
                if conservative:
                    raise ValueError("conservative update is a count-min "
                                     "mode; kind='exact' counts are already "
                                     "exact")
                if (grid_step, grid_origin) != (1.0, 0.0):
                    raise ValueError("grid_step/grid_origin are count-min "
                                     "parameters; kind='exact' enumerates "
                                     "its actual codes and needs no grid")
                self.categoricals[column] = CategoricalSketch(
                    max_codes=max_codes)
            elif kind == "cm":
                # seed from the column name alone (NOT the per-host store
                # seed): cross-host merge adds the counter tables cell-wise,
                # which is only meaningful when every host hashes codes
                # identically
                self.categoricals[column] = CountMinSketch(
                    width=width, depth=depth,
                    seed=zlib.crc32(column.encode()) % 1000,
                    conservative=conservative,
                    grid_step=grid_step, grid_origin=grid_origin)
            else:
                raise ValueError(f"unknown sketch kind {kind!r}; "
                                 f"expected one of {sorted(_SKETCH_KINDS)}")

    def subscribe(self, fn: Callable[[Dict[ColumnKey, int]], None]
                  ) -> Callable[[], None]:
        """Version-change notification: `fn` is called after every
        `add_batch` with {column-or-joint-tuple: new version} for each bumped
        reservoir.  Returns an unsubscribe callable.  Admission sessions use
        this to re-key in-flight micro-batches to the fresh synopsis."""
        with self._write_lock:
            self._listeners.append(fn)

        def unsubscribe() -> None:
            with self._write_lock:
                try:
                    self._listeners.remove(fn)
                except ValueError:
                    pass
        return unsubscribe

    def _register_session(self, session) -> None:
        """Track an admission session (weakly) so `stats()` can aggregate its
        counters; called by AqpSession.__init__."""
        with self._write_lock:
            self._sessions = [r for r in self._sessions
                              if r() is not None]
            self._sessions.append(weakref.ref(session))

    def add_batch(self, stats: Dict[str, np.ndarray]) -> None:
        rows = max((np.size(v) for v in stats.values()), default=0)
        with obs.span("store.insert", rows=rows):
            self._add_batch(stats)

    def _add_batch(self, stats: Dict[str, np.ndarray]) -> None:
        # Build joint rows BEFORE mutating any reservoir: a ragged batch must
        # fail cleanly, not leave per-column reservoirs updated with the
        # joints skipped (partial mutation would silently skew every joint
        # synopsis fitted afterwards).
        joint_rows = {}
        for cols in self.joints:
            if all(c in stats for c in cols):
                arrays = [np.asarray(stats[c], np.float32).ravel() for c in cols]
                sizes = {c: a.shape[0] for c, a in zip(cols, arrays)}
                if len(set(sizes.values())) > 1:
                    raise ValueError(f"joint {cols} needs row-aligned columns, "
                                     f"got lengths {sizes}")
                joint_rows[cols] = np.stack(arrays, axis=1)
        t_ingest = time.perf_counter() if obs.enabled() else 0.0
        with self._write_lock:      # vs to_state: snapshots see whole batches
            for name, values in stats.items():
                if name not in self.columns:
                    self.columns[name] = Reservoir(self.capacity,
                                                   seed=self._col_seed(name))
                res = self.columns[name]
                res.add(values)
                n_rows = np.asarray(values).size
                self.metrics.counter("aqp.ingest.rows", column=name).inc(
                    n_rows)
                self.metrics.gauge("aqp.reservoir.fill", column=name).set(
                    res.n_filled / max(res.capacity, 1))
                sketch = self.categoricals.get(name)
                if sketch is not None:
                    sketch.add(values)
                    eb = getattr(sketch, "err_bound", None)
                    if eb is not None:
                        self.metrics.gauge("aqp.sketch.err_bound",
                                           column=name).set(eb())
            for cols, rows in joint_rows.items():
                self.joints[cols].add(rows)
            self.metrics.counter("aqp.ingest.batches").inc()
            if self._listeners:
                bumped: Dict[ColumnKey, int] = {
                    name: self.columns[name].version for name in stats}
                for cols in joint_rows:
                    bumped[cols] = self.joints[cols].version
                for fn in list(self._listeners):
                    fn(bumped)
        if t_ingest:
            self.metrics.histogram("aqp.ingest.us").observe(
                (time.perf_counter() - t_ingest) * 1e6)

    def synopsis(self, column: str, selector: str = "plugin",
                 tier: Optional[int] = None,
                 backend: str = "jnp") -> KDESynopsis:
        """A column's synopsis; `backend` as for `joint_synopsis`."""
        res = self.columns.get(column)
        if res is None:
            raise KeyError(f"unknown column {column!r}; "
                           f"have {sorted(self.columns)}")
        return self._fit_cached(column, res, selector, tier=tier,
                                backend=backend)

    def joint_synopsis(self, columns: Sequence[str],
                       selector: str = "plugin",
                       tier: Optional[int] = None,
                       backend: str = "jnp") -> KDESynopsis:
        """Joint synopsis over a tracked column tuple: per-axis diagonal
        bandwidths (plugin/silverman/LSCV_h), or full-H LSCV_H.

        `backend` ("jnp" | "pallas") runs the PLUGIN and LSCV_h pair sums;
        LSCV_H fits on jnp.  A cached synopsis serves either backend: both
        compute the same estimator to float32 rounding."""
        key = tuple(columns)
        res = self.joints.get(key)
        if res is None:
            raise KeyError(f"no joint reservoir for columns {key!r}; call "
                           f"track_joint({key!r}) before add_batch "
                           f"(have {sorted(self.joints)})")
        return self._fit_cached(key, res, selector, tier=tier,
                                backend=backend)

    def _fit_cached(self, key: ColumnKey, res: Reservoir, selector: str,
                    tier: Optional[int] = None,
                    backend: str = "jnp") -> KDESynopsis:
        # lazy import: aqp_query imports this module's types at top level
        from repro.core.aqp_query import _effective_tier, _tier_key

        selector = canonical_selector(selector)
        tier = _effective_tier(res, tier)
        ckey = _tier_key(key, tier)
        syn, stale = self.cache.lookup(ckey, selector, res.version)
        if syn is None:
            data = res.sample() if tier is None else res.sample(tier)
            n, d = data.shape[0], (data.shape[1] if data.ndim > 1 else 1)
            # PLUGIN and LSCV_h run their pair sums on the caller's backend;
            # LSCV_H keeps its jnp Nelder-Mead
            backend = backend if selector in ("plugin", "lscv_h") else "jnp"
            grid = {}
            if selector == "lscv_h":        # d one-axis grid searches
                grid = {"n_h": N_H_DEFAULT,
                        "pair_terms": d * n * (n - 1) // 2 * N_H_DEFAULT}
            with obs.span("synopsis.fit", n=n, d=d, selector=selector,
                          backend=backend, pallas=int(backend == "pallas"),
                          **grid):
                syn = KDESynopsis.fit(data, selector=selector,
                                      max_sample=self.capacity,
                                      backend=backend)
            obs.get_registry().counter("aqp.synopsis.fits", selector=selector,
                                       stale=int(stale)).inc()
            # scale against the FULL stream: every tier is a uniform sample
            # of it, so tier answers are unbiased for the same relation
            syn.n_source = res.n_seen
            self.cache.put(ckey, selector, res.version, syn)
        return syn

    # -- queries ------------------------------------------------------------
    #
    # `query` is the one entry point: a mixed batch of declarative AqpQuery
    # specs (1-D ranges, multi-d boxes, categorical Eq terms, GROUP BY) is
    # planned and executed by the QueryEngine facade.  `query_batch` /
    # `query_box_batch` are retained conveniences for the legacy Query /
    # BoxQuery types; they compile to the same engine.

    def engine(self, **kwargs) -> "QueryEngine":
        """A fresh QueryEngine facade over this store (repro.core.aqp_query).
        Prefer `shared_engine` for repeated querying — it keeps one PlanCache
        per (selector, backend) that checkpoints ride along with."""
        from repro.core.aqp_query import QueryEngine
        return QueryEngine(self, **kwargs)

    def shared_engine(self, selector: str = "plugin",
                      backend: str = "jnp") -> "QueryEngine":
        """The store-owned engine for (selector, backend), created on first
        use.  Its PlanCache persists across `query()` calls and through
        `to_state`/`restore_state`, so a warm-started store replays cached
        plans instead of replanning on its first flush."""
        key = (canonical_selector(selector), backend)
        # get-or-create under _write_lock: two racing callers must share one
        # engine (and one PlanCache), not last-writer-wins two
        with self._write_lock:
            eng = self._engines.get(key)
            if eng is None:
                eng = self.engine(selector=key[0], backend=backend)
                self._engines[key] = eng
            return eng

    def session(self, selector: str = "plugin", backend: str = "jnp",
                **kwargs) -> "AqpSession":
        """A streaming admission session over this store: submit AqpQuery
        specs from many logical clients, micro-batches coalesce across
        callers and flush on watermark/deadline (repro.core.aqp_admission).
        Remaining kwargs (watermark, max_delay, ...) go to AqpSession."""
        return self.shared_engine(selector, backend).session(**kwargs)

    def query(self, queries, selector: str = "plugin",
              backend: str = "jnp", mode: str = "batch"):
        """Answer a mixed batch of AqpQuery specs in one engine call; returns
        AqpResult rows (estimate + path + confidence interval + synopsis
        version) in submission order.  `mode="progressive"` returns the
        engine's (tier, results) generator instead (see
        `QueryEngine.progressive`)."""
        return self.shared_engine(selector, backend).execute(queries,
                                                             mode=mode)

    def count(self, column: str, a: float, b: float, selector: str = "plugin") -> float:
        return float(self.synopsis(column, selector).count(a, b))

    def avg(self, column: str, a: float, b: float, selector: str = "plugin") -> float:
        return float(self.synopsis(column, selector).avg(a, b))

    def fraction(self, column: str, a: float, b: float, selector: str = "plugin") -> float:
        res = self.columns[column]
        return self.count(column, a, b, selector) / max(res.n_seen, 1)

    def query_batch(self, queries: Sequence[Query], selector: str = "plugin",
                    backend: str = "jnp") -> np.ndarray:
        """Answer N legacy 1-D range queries (mixed ops/ranges/columns)
        through the unified engine; synopses come from the cache."""
        from repro.core.aqp_query import QueryEngine, from_query

        queries = [q if isinstance(q, Query) else Query(*q) for q in queries]
        specs = [from_query(q) for q in queries]
        return QueryEngine(self, selector=selector,
                           backend=backend).answers(specs)

    def query_box_batch(self, queries: Sequence[BoxQuery],
                        selector: str = "plugin",
                        backend: str = "jnp") -> np.ndarray:
        """Answer N legacy multi-column box queries (eq. 11) through the
        unified engine; joint synopses come from the cache."""
        from repro.core.aqp_query import QueryEngine, from_box_query

        queries = [q if isinstance(q, BoxQuery) else BoxQuery(*q)
                   for q in queries]
        specs = [from_box_query(q) for q in queries]
        return QueryEngine(self, selector=selector,
                           backend=backend).answers(specs)

    def stats(self) -> Dict[str, object]:
        """Store-level observability: cache hit/miss/eviction counters,
        per-reservoir stream sizes, which joints were seeded by the
        per-column backfill (pseudo-rows, see `track_joint`), exact-sketch
        coverage, and aggregated admission-session counters."""
        cats = {}
        for name, sketch in self.categoricals.items():
            ent = sketch.stats()
            res = self.columns.get(name)
            ent["exact"] = res is not None and sketch.exact_for(res.n_seen)
            cats[name] = ent
        return {
            "cache": self.cache.stats(),
            "columns": {name: res.n_seen for name, res in self.columns.items()},
            "joints": {key: res.n_seen for key, res in self.joints.items()},
            "backfilled": {key: res.backfilled
                           for key, res in self.joints.items()},
            "categoricals": cats,
            "admission": self._admission_stats(),
        }

    def _admission_stats(self) -> Dict[str, object]:
        """Aggregate admission counters across every session ever opened on
        this store, summed straight from the metrics registry.

        The pre-registry implementation iterated live weakrefs and summed
        `session.stats()` dicts, so a session that was closed and
        garbage-collected took its counters with it — the store-level totals
        silently dropped whole sessions' worth of work (and double-counted
        nothing only by luck of GC timing).  Registry counters are labelled
        `session=<id>` and outlive the session object, so the sums here are
        monotone regardless of session lifetime; only `sessions` (currently
        registered) and `pending` (live depth gauges) reflect the present.
        """
        with self._write_lock:
            live = [r for r in self._sessions if r() is not None]
        reg = self.metrics
        agg: Dict[str, object] = {"sessions": len(live)}
        for k in ("submitted", "executed", "flushes", "coalesced",
                  "invalidations", "blocked", "shed", "fit_requeued"):
            agg[k] = int(reg.sum_counter(f"aqp.admission.{k}"))
        agg["pending"] = int(reg.sum_gauge("aqp.admission.depth"))
        flush_reasons: Dict[str, int] = {}
        for labels, n in reg.collect_counters("aqp.admission.flush_reason"):
            reason = labels.get("reason", "?")
            flush_reasons[reason] = flush_reasons.get(reason, 0) + int(n)
        agg["flush_reasons"] = flush_reasons
        batch_rows = reg.sum_counter("aqp.admission.batch_rows")
        agg["mean_batch"] = (batch_rows / agg["flushes"]
                             if agg["flushes"] else 0.0)
        return agg

    def merge(self, other: "TelemetryStore") -> "TelemetryStore":
        out = TelemetryStore(self.capacity, self.seed,
                             cache_entries=self.cache.max_entries,
                             cache_bytes=self.cache.max_bytes)
        for name in set(self.columns) | set(other.columns):
            if name in self.columns and name in other.columns:
                out.columns[name] = self.columns[name].merge(other.columns[name])
            else:
                # deep copy: the merged store is a snapshot, so later updates
                # to the source store must not leak into it through aliasing
                out.columns[name] = copy.deepcopy(
                    self.columns.get(name) or other.columns[name])
        for key in set(self.joints) | set(other.joints):
            if key in self.joints and key in other.joints:
                out.joints[key] = self.joints[key].merge(other.joints[key])
            else:
                out.joints[key] = copy.deepcopy(
                    self.joints.get(key) or other.joints[key])
        for name in set(self.categoricals) | set(other.categoricals):
            if name in self.categoricals and name in other.categoricals:
                out.categoricals[name] = \
                    self.categoricals[name].merge(other.categoricals[name])
            else:
                # one-sided sketch: carried along, but it cannot cover the
                # merged stream, so `exact_for` disables the exact path
                out.categoricals[name] = copy.deepcopy(
                    self.categoricals.get(name) or other.categoricals[name])
        return out

    # -- durability ----------------------------------------------------------
    #
    # `to_state`/`from_state` round-trip the store's complete mutable state;
    # `save`/`load` put it behind the atomic keep-k CheckpointManager.  The
    # fitted synopses in the cache ride along, so a warm-started store skips
    # the expensive bandwidth refits entirely (the paper's whole premise is
    # that fitting is the step worth not repeating).

    def to_state(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        """Snapshot to (flat array tree, JSON-safe metadata), taken under the
        store's write lock — a snapshot racing `add_batch` sees whole batches
        only, so a persisted sketch never claims rows its reservoir has not
        seen (`from_state` re-asserts this invariant on load)."""
        with self._write_lock:
            tree: Dict[str, np.ndarray] = {}
            meta: Dict[str, object] = {
                "format": STATE_FORMAT, "capacity": int(self.capacity),
                "seed": int(self.seed), "columns": {}, "joints": [],
                "categoricals": {}, "cache": [],
            }
            for name in list(self.columns) + list(self.categoricals):
                if "/" in name:
                    raise ValueError(f"column name {name!r} contains '/', "
                                     f"which state keys reserve as a "
                                     f"separator")
            for name, res in self.columns.items():
                if isinstance(res, TieredReservoir):
                    arrays, m = res.state()
                    for k, arr in arrays.items():
                        tree[f"columns/{name}/{k}"] = arr
                else:
                    buf, m = res.state()
                    tree[f"columns/{name}/buf"] = buf
                meta["columns"][name] = m
            for i, (cols, res) in enumerate(self.joints.items()):
                if isinstance(res, TieredReservoir):
                    arrays, m = res.state()
                    for k, arr in arrays.items():
                        tree[f"joints/{i}/{k}"] = arr
                else:
                    buf, m = res.state()
                    tree[f"joints/{i}/buf"] = buf
                m["columns"] = list(cols)
                meta["joints"].append(m)
            for name, sketch in self.categoricals.items():
                arrays, m = sketch.state()
                for k, arr in arrays.items():
                    tree[f"categoricals/{name}/{k}"] = arr
                meta["categoricals"][name] = m
            for i, (key, version, syn) in enumerate(self.cache.entries()):
                col, sel = key
                ent = {
                    "column": list(col) if isinstance(col, tuple) else col,
                    "is_tuple": isinstance(col, tuple), "selector": sel,
                    "version": int(version), "n_source": int(syn.n_source),
                    "syn_selector": syn.selector,
                }
                to_state = getattr(syn, "to_state", None)
                if to_state is not None:
                    # pluggable `repro.synopses` backend (e.g. a fitted RFF
                    # state): it serializes itself; the backend name in the
                    # meta picks the deserializer on restore
                    arrays, syn_meta = to_state()
                    ent["synopsis"] = syn_meta
                    for k, arr in arrays.items():
                        tree[f"cache/{i}/{k}"] = np.asarray(arr)
                else:
                    tree[f"cache/{i}/x"] = np.asarray(syn.x)
                    if syn.h is not None:
                        tree[f"cache/{i}/h"] = np.asarray(syn.h)
                    if syn.H is not None:
                        tree[f"cache/{i}/H"] = np.asarray(syn.H)
                meta["cache"].append(ent)
            # shared engines' plan-cache keys ride along: plans rebuild from
            # the persisted synopses on restore, so warm starts skip the
            # compile-and-plan pass too (not just the bandwidth fits)
            meta["plans"] = []
            for (sel_eng, backend), eng in self._engines.items():
                entries = []
                for key, version in eng.plans.entries():
                    if not (isinstance(key, tuple) and len(key) == 3):
                        continue      # mapping-resolver keys: not durable
                    col, sel, tier = key
                    entries.append({
                        "column": list(col) if isinstance(col, tuple)
                        else col,
                        "is_tuple": isinstance(col, tuple),
                        "selector": sel, "tier": tier,
                        "version": int(version)})
                if entries:
                    meta["plans"].append({"selector": sel_eng,
                                          "backend": backend,
                                          "entries": entries})
            # the registry rides in the (JSON) manifest so cumulative
            # counters — ingest rows, admission totals — survive a restart
            meta["metrics"] = self.metrics.state()
            return tree, meta

    def restore_state(self, tree: Dict[str, np.ndarray],
                      meta: Dict[str, object]) -> None:
        """Swap this store's contents for a snapshot's, in place.  The
        restored reservoir versions are pushed through the `subscribe`
        listeners, so in-flight admission buckets re-key to them and the
        version-keyed PlanCache/SynopsisCache lookups key correctly."""
        import jax.numpy as jnp

        if int(meta.get("format", -1)) != STATE_FORMAT:
            raise ValueError(f"unsupported store-state format "
                             f"{meta.get('format')!r} (want {STATE_FORMAT})")
        with self._write_lock:
            self.capacity = int(meta["capacity"])

            def _subtree(prefix: str) -> Dict[str, np.ndarray]:
                return {k[len(prefix):]: v for k, v in tree.items()
                        if k.startswith(prefix)}

            columns: Dict[str, Reservoir] = {}
            for name, m in meta["columns"].items():
                if m.get("kind") == "tiered":
                    columns[name] = TieredReservoir.from_state(
                        _subtree(f"columns/{name}/"), m)
                    continue
                res = Reservoir(self.capacity, seed=self._col_seed(name))
                res.load_state(tree[f"columns/{name}/buf"], m)
                columns[name] = res
            joints: Dict[Tuple[str, ...], MultiReservoir] = {}
            for i, m in enumerate(meta["joints"]):
                cols = tuple(m["columns"])
                if m.get("kind") == "tiered":
                    joints[cols] = TieredReservoir.from_state(
                        _subtree(f"joints/{i}/"), m)
                    continue
                res = MultiReservoir(cols, self.capacity,
                                     seed=self._col_seed("|".join(cols)))
                res.load_state(tree[f"joints/{i}/buf"], m)
                joints[cols] = res
            categoricals: Dict[str, object] = {}
            for name, m in meta["categoricals"].items():
                prefix = f"categoricals/{name}/"
                arrays = {k[len(prefix):]: v for k, v in tree.items()
                          if k.startswith(prefix)}
                sketch = _SKETCH_KINDS[str(m["kind"])].from_state(arrays, m)
                res = columns.get(name)
                if res is not None and sketch.n_rows > res.n_seen:
                    # the coverage invariant: restoring this would let the
                    # store claim exact coverage of rows it never sampled
                    raise ValueError(
                        f"inconsistent snapshot: sketch for {name!r} has "
                        f"seen {sketch.n_rows} rows but its reservoir only "
                        f"{res.n_seen}")
                categoricals[name] = sketch
            self.columns = columns
            self.joints = joints
            self.categoricals = categoricals
            self.cache.invalidate()
            for i, ent in enumerate(meta["cache"]):
                syn_meta = ent.get("synopsis")
                if syn_meta is not None:
                    # pluggable backend entry: round-trip through its own
                    # (de)serializer, bit-for-bit (test-enforced for RFF)
                    from repro.synopses import get_backend
                    syn = get_backend(str(syn_meta["backend"])).from_state(
                        _subtree(f"cache/{i}/"), syn_meta)
                    syn.n_source = int(ent["n_source"])
                    syn.selector = str(ent["syn_selector"])
                else:
                    h = tree.get(f"cache/{i}/h")
                    H = tree.get(f"cache/{i}/H")
                    syn = KDESynopsis(
                        x=jnp.asarray(tree[f"cache/{i}/x"]),
                        h=None if h is None else jnp.asarray(h),
                        H=None if H is None else jnp.asarray(H),
                        n_source=int(ent["n_source"]),
                        selector=str(ent["syn_selector"]))
                col = tuple(ent["column"]) if ent["is_tuple"] \
                    else ent["column"]
                self.cache.put(col, str(ent["selector"]),
                               int(ent["version"]), syn)
            # rebuild shared-engine plans eagerly from the restored synopses
            # (NOT through SynopsisCache.get — priming must not count as
            # misses, the warm-start contract is zero cache misses)
            self._engines = {}
            if meta.get("plans"):
                from repro.core.aqp_query import _make_plan, _tier_key

                index = {key: (v, syn)
                         for key, v, syn in self.cache.entries()}
                for peng in meta["plans"]:
                    eng = self.shared_engine(str(peng["selector"]),
                                             str(peng["backend"]))
                    for ent in peng["entries"]:
                        col = tuple(ent["column"]) if ent["is_tuple"] \
                            else ent["column"]
                        tier = ent["tier"]
                        tier = None if tier is None else int(tier)
                        hit = index.get((_tier_key(col, tier),
                                         str(ent["selector"])))
                        if hit is not None and hit[0] == int(ent["version"]):
                            eng.plans.put((col, str(ent["selector"]), tier),
                                          int(ent["version"]),
                                          _make_plan(hit[1]))
            # optional key: pre-observability snapshots restore fine; the
            # gauges mirrored from live structures (cache size, reservoir
            # fill) are restored too but refresh on the next mutation
            if meta.get("metrics"):
                self.metrics.load_state(meta["metrics"])
            if self._listeners:
                bumped: Dict[ColumnKey, int] = {
                    name: res.version for name, res in self.columns.items()}
                for cols, res in self.joints.items():
                    bumped[cols] = res.version
                for fn in list(self._listeners):
                    fn(bumped)

    @classmethod
    def from_state(cls, tree: Dict[str, np.ndarray],
                   meta: Dict[str, object], cache_entries: int = 128,
                   cache_bytes: Optional[int] = None) -> "TelemetryStore":
        """Rebuild a store from a `to_state` snapshot."""
        store = cls(capacity=int(meta["capacity"]), seed=int(meta["seed"]),
                    cache_entries=cache_entries, cache_bytes=cache_bytes)
        store.restore_state(tree, meta)
        return store

    def save(self, path: str, step: Optional[int] = None,
             keep: int = 3) -> int:
        """Write an atomic snapshot under `path` through the keep-k
        `CheckpointManager` (crash mid-write never corrupts the latest
        completed snapshot).  Returns the step written (monotonic when
        `step` is omitted)."""
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager(path, keep=keep, async_save=False)
        if step is None:
            latest = mgr.latest_step()
            step = 1 if latest is None else latest + 1
        t0 = time.perf_counter()
        tree, meta = self.to_state()
        mgr.save(step, tree, extra=meta)
        self.metrics.histogram("aqp.snapshot.us").observe(
            (time.perf_counter() - t0) * 1e6)
        return step

    @classmethod
    def load(cls, path: str, step: Optional[int] = None,
             cache_entries: int = 128,
             cache_bytes: Optional[int] = None) -> "TelemetryStore":
        """Warm-start a store from the latest (or a specific) snapshot under
        `path`.  Everything survives: reservoir samples and RNG states (so
        post-restore sampling is bit-identical to an uninterrupted store),
        versions, joint registrations and backfill flags, categorical-sketch
        coverage (exact-Eq answers stay exact), and the fitted synopses."""
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager(path, async_save=False)
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no completed snapshots under "
                                        f"{path!r}")
        tree, meta = mgr.restore_flat(step)
        return cls.from_state(tree, meta, cache_entries=cache_entries,
                              cache_bytes=cache_bytes)
