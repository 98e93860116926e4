"""Unified declarative AQP query API — one spec, one engine, many paths.

After the 1-D (`Query`/`QueryBatch`) and multi-d (`BoxQuery`/`BoxQueryBatch`)
stacks, this module makes the *query surface* the product (cf. VerdictDB's
single logical query interface over many execution backends, and DEANN's
estimator-contract / acceleration-backend split):

  `AqpQuery`   — a declarative aggregate: COUNT/SUM/AVG under a conjunction of
                 predicate terms, optionally grouped by a dictionary column.
      Range(column, a, b)   a <= column <= b        (eqs. 9-10 closed forms)
      Box(columns, lo, hi)  axis-aligned box        (eq. 11 product kernel)
      Eq(column, value)     dictionary/categorical equality (code +- 1/2)
  `QueryEngine` — the facade over a `TelemetryStore`: normalizes/validates a
                 heterogeneous batch, groups it by (column tuple, selector),
                 and routes each group to the cheapest applicable path:

      path      synopsis                 kernel
      -------   ----------------------   -----------------------------------
      range1d   1-D sample, scalar h     closed forms (`batch_query_1d`, or
                                         the Pallas `aqp_batch` tile kernel)
      box       rows, diagonal h         eq. 11 product kernel
                                         (`batch_query_box` / Pallas
                                         `aqp_boxes` tiles)
      qmc       full bandwidth matrix H  batched quasi-MC: shared Halton
                                         nodes, ONE KDE pass per group
                                         (`batch_query_qmc`)

  `AqpResult`  — estimate + the chosen path, a relative-width accuracy proxy,
                 and the synopsis version that answered the query.

The legacy stacks survive as deprecated shims: `QueryBatch.run` /
`BoxQueryBatch.run` compile their queries to `AqpQuery` specs and execute
through this module, bit-for-bit identical to `QueryEngine.execute`.
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.tuning import env_int

from .aqp import (OP_CODES, OP_COUNT, OP_SUM, KDESynopsis,
                  batch_query_1d, canonical_selector)
from .aqp_ci import (DEFAULT_CI_LEVEL, moments_1d, moments_box, norm_ppf,
                     qmc_subsample_se, se_from_moments, t_ppf)
from .aqp_multid import (batch_query_box, batch_query_box_grouped,
                         batch_query_qmc, batch_query_qmc_rff, qmc_rff_se)

ColumnKey = Union[None, str, Tuple[str, ...]]

EQ_HALFWIDTH = 0.5   # dictionary codes are unit-spaced: `== v` is v +- 1/2
WIDE = 1e30          # "unconstrained axis": Phi saturates to {0,1}, phi to 0

# --- density-synopsis backend selection (repro.synopses) --------------------
#
# The quasi-MC path's density pass is pluggable: "exact" is the direct
# kde_eval_H evaluation (O(n) per node, bit-identical to the pre-backend
# engine), "rff" the sublinear random-Fourier-feature synopsis (O(D) per
# node after an O(n*D) once-per-version fit).  "auto" picks by sample size:
# below the crossover the exact pass is already cheap and the RFF fit would
# never amortize.
KDE_BACKENDS = ("auto", "exact", "rff")
# Module constants are the *defaults*; the env knobs are re-read per call
# (an import-time env_int froze them before a late env change could move
# them — the same bug PR 9 fixed for kernel tiles).  Tests monkeypatch the
# constants; the env vars still win when set.
KDE_CROSSOVER = 16384
DEFAULT_RFF_FEATURES = 2048
# one-shot empirical accuracy gate at fit time: mean relative density error
# on probe points from the fitted sample; above tolerance the synopsis is
# marked degraded and the group falls back to the exact pass (counted)
RFF_GATE_PROBES = 32
RFF_GATE_TOL = 0.15


def _kde_crossover() -> int:
    return env_int("REPRO_KDE_CROSSOVER", KDE_CROSSOVER)


def _rff_features() -> int:
    return env_int("REPRO_RFF_FEATURES", DEFAULT_RFF_FEATURES)


def _resolve_kde_backend(requested: Optional[str], default: str,
                         n: int) -> str:
    name = requested or default or "auto"
    if name == "auto":
        return "rff" if n >= _kde_crossover() else "exact"
    return name


def _rff_cache_key(col, n_features: int):
    """SynopsisCache column key for a fitted RFF synopsis — suffixed like
    `_tier_key` so RFF state coexists with the exact synopsis entry and
    round-trips the checkpoint serializer untouched."""
    if isinstance(col, tuple):
        return col + (f"#rff{n_features}",)
    return f"{col}#rff{n_features}"


# --- tier addressing (TieredReservoir, repro.data.aqp_store) ----------------

def _effective_tier(res, tier: Optional[int]) -> Optional[int]:
    """Normalize a tier request against a reservoir: None (or a plain
    untiered reservoir) means the full sample, and a request for the top
    tier of a `TieredReservoir` collapses to None too — the top tier IS the
    full sample, so full-accuracy requests share cache keys, plans, and
    jitted executables with untiered execution."""
    n_tiers = getattr(res, "n_tiers", None)
    if tier is None or n_tiers is None:
        return None
    t = max(0, min(int(tier), n_tiers - 1))
    return None if t >= n_tiers - 1 else t


def _tier_key(col, tier: Optional[int]):
    """Suffix a synopsis-cache column key with the tier so tiered synopses
    coexist with the full-sample entry.  '#' cannot appear in a tracked
    column tuple's joint (names are user column names), and the suffixed key
    round-trips through the checkpoint cache serialization untouched."""
    if tier is None:
        return col
    if isinstance(col, tuple):
        return col + (f"#tier{tier}",)
    return f"{col}#tier{tier}"


# --- predicate terms --------------------------------------------------------

@dataclass(frozen=True)
class Range:
    """a <= column <= b.  `column=None` addresses a bare (unnamed) synopsis."""
    column: Optional[str]
    a: float
    b: float

    def __post_init__(self):
        object.__setattr__(self, "a", float(self.a))
        object.__setattr__(self, "b", float(self.b))


@dataclass(frozen=True)
class Eq:
    """Dictionary/categorical equality: column == value.

    Dictionary-coded columns hold unit-spaced numeric codes, so equality is
    the range [value - halfwidth, value + halfwidth] over the code axis — the
    KDE mass the synopsis assigns to that code's bucket.
    """
    column: Optional[str]
    value: float
    halfwidth: float = EQ_HALFWIDTH

    def __post_init__(self):
        object.__setattr__(self, "value", float(self.value))
        object.__setattr__(self, "halfwidth", float(self.halfwidth))
        if self.halfwidth <= 0:
            raise ValueError(f"Eq halfwidth must be positive, got {self.halfwidth}")


@dataclass(frozen=True)
class Box:
    """Axis-aligned box: lo_j <= columns_j <= hi_j.  `columns=None` addresses
    the positional axes of a bare (unnamed) multi-d synopsis."""
    columns: Optional[Tuple[str, ...]]
    lo: Tuple[float, ...]
    hi: Tuple[float, ...]

    def __post_init__(self):
        object.__setattr__(self, "lo", tuple(float(v) for v in np.ravel(self.lo)))
        object.__setattr__(self, "hi", tuple(float(v) for v in np.ravel(self.hi)))
        if len(self.lo) != len(self.hi):
            raise ValueError(f"lo/hi dimensionality mismatch: "
                             f"{len(self.lo)} vs {len(self.hi)}")
        if self.columns is not None:
            object.__setattr__(self, "columns", tuple(self.columns))
            if len(self.columns) != len(self.lo):
                raise ValueError(f"box has {len(self.lo)} axes but names "
                                 f"{len(self.columns)} columns")


Predicate = Union[Range, Box, Eq]


@dataclass(frozen=True)
class GroupBy:
    """GROUP BY over a dictionary column.  `values=None` discovers the code
    set from the store's reservoir sample at execution time."""
    column: str
    values: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        if self.values is not None:
            object.__setattr__(self, "values",
                               tuple(float(v) for v in self.values))


@dataclass(frozen=True)
class AqpQuery:
    """One declarative aggregate: COUNT/SUM/AVG of `target` under the
    conjunction of `predicates`, optionally per `group_by` category.

    `selector` overrides the engine's bandwidth selector for this query only
    (e.g. one `lscv_H` query inside a `plugin` batch routes to the quasi-MC
    path while the rest stay on the closed forms).

    `kde_backend` overrides the engine's density-synopsis backend for this
    query only ("auto" | "exact" | "rff"); it matters only on the quasi-MC
    (full-H) path and is ignored by the closed-form and exact-sketch paths.
    """
    aggregate: str                               # "count" | "sum" | "avg"
    predicates: Tuple[Predicate, ...] = ()
    target: Optional[Union[str, int]] = None     # SUM/AVG column (or axis)
    group_by: Optional[Union[str, "GroupBy"]] = None
    selector: Optional[str] = None               # per-query selector override
    kde_backend: Optional[str] = None            # per-query density backend

    def __post_init__(self):
        if self.kde_backend is not None:
            kb = str(self.kde_backend).lower()
            if kb not in KDE_BACKENDS:
                raise ValueError(f"unknown kde_backend {self.kde_backend!r}; "
                                 f"expected one of {KDE_BACKENDS}")
            object.__setattr__(self, "kde_backend", kb)
        agg = str(self.aggregate).lower()
        if agg not in OP_CODES:
            raise ValueError(f"unknown aggregate {self.aggregate!r}; "
                             f"expected one of {sorted(OP_CODES)}")
        object.__setattr__(self, "aggregate", agg)
        preds = self.predicates
        if isinstance(preds, (Range, Box, Eq)):
            preds = (preds,)
        preds = tuple(preds)
        for p in preds:
            if not isinstance(p, (Range, Box, Eq)):
                raise TypeError(f"predicate terms must be Range/Box/Eq, "
                                f"got {type(p).__name__}")
        object.__setattr__(self, "predicates", preds)
        if isinstance(self.group_by, str):
            object.__setattr__(self, "group_by", GroupBy(self.group_by))
        if self.group_by is not None and not isinstance(self.group_by, GroupBy):
            raise TypeError("group_by must be a column name or GroupBy")
        if agg == "count":
            if self.target is not None:
                raise ValueError("COUNT takes no target column")
            if not preds and self.group_by is None:
                raise ValueError("COUNT needs at least one predicate term")
        elif not preds and self.target is None:
            raise ValueError("SUM/AVG needs a predicate term or a target column")


@dataclass(frozen=True)
class AqpResult:
    """One answered aggregate.

    estimate         — the approximate answer
    path             — execution path: "range1d" | "box" | "qmc" | "exact"
                       | "exact:cm" (":pallas" suffix when the Pallas tile
                       kernels ran; "box:grouped" for GROUP BY families
                       answered by the factored grouped kernel; "exact"
                       answers come from a CategoricalSketch, "exact:cm"
                       from a bounded-error CountMinSketch — not the KDE;
                       "qmc:rff" when the full-H density pass ran on the
                       sublinear random-Fourier-feature synopsis backend)
    ci_lo / ci_hi    — confidence interval at `ci_level`, computed per path:
                       analytic product-kernel variance for range1d/box (and
                       box:grouped), subsample (batch-means) variance for
                       qmc, exact zero width for "exact", and the count-min
                       error bound for "exact:cm".  Infinite endpoints mean
                       the estimate carries no finite error bound (e.g. AVG
                       over an effectively empty selection).
    ci_level         — nominal coverage of [ci_lo, ci_hi] (default 0.95)
    n_effective      — rows behind the answer: the retained sample size for
                       the KDE paths (the tier size under a tier budget),
                       the sketch's full row count for the exact paths
    rel_width        — DEPRECATED accuracy proxy (narrowest constrained axis
                       in bandwidths, min_j (hi_j - lo_j) / h_j); kept for
                       one release, prefer the CI fields.  0.0 on the exact
                       paths (no smoothing at all); inf only when no axis is
                       constrained (whole-table SUM/AVG).
    synopsis_version — reservoir version of the synopsis that answered it
                       (0 when executed against bare synopses, not a store)
    group            — group_by category code (None outside GROUP BY)
    query            — the originating AqpQuery spec
    """
    estimate: float
    path: str
    rel_width: float
    synopsis_version: int
    group: Optional[float] = None
    query: Optional[AqpQuery] = None
    ci_lo: float = float("nan")
    ci_hi: float = float("nan")
    ci_level: float = DEFAULT_CI_LEVEL
    n_effective: int = 0

    @property
    def ci_width(self) -> float:
        return self.ci_hi - self.ci_lo

    def __float__(self) -> float:
        return self.estimate


# --- normalization: AqpQuery -> one axis-aligned box per (sub-)query --------

@dataclass
class _Compiled:
    """One execution unit: an axis-aligned box (possibly with wide, i.e.
    unconstrained, axes) plus the aggregate opcode and target axis."""
    slot: int                            # output row
    query: AqpQuery
    group: Optional[float]
    cols: Optional[Tuple[str, ...]]      # None -> positional (bare synopsis)
    lo: List[float]
    hi: List[float]
    constrained: List[bool]              # wide target fills are False
    op: int
    tgt: int
    selector: Optional[str]
    all_eq: bool = False                 # every interval is a code window
    group_axis: Optional[int] = None     # axis of the group_by column
    kde_backend: Optional[str] = None    # per-query density backend


def _compile(query: AqpQuery, slot: int,
             group_value: Optional[float] = None) -> _Compiled:
    """Normalize one query (plus its group term) to a canonical box: terms
    merge per column by interval intersection, SUM/AVG targets outside the
    predicate columns get a wide (unconstrained) axis."""
    intervals: "Dict[Union[str, int], List]" = {}
    eq_only: "Dict[Union[str, int], bool]" = {}
    named: Optional[bool] = None

    def add(key, lo_v, hi_v, is_named, is_eq=False):
        nonlocal named
        if named is None:
            named = is_named
        elif named != is_named:
            raise ValueError("cannot mix named and positional (column=None) "
                             "predicate terms in one AqpQuery")
        eq_only[key] = eq_only.get(key, True) and is_eq
        ent = intervals.get(key)
        if ent is None:
            intervals[key] = [float(lo_v), float(hi_v), True]
        else:
            ent[0] = max(ent[0], float(lo_v))
            ent[1] = min(ent[1], float(hi_v))
            if ent[1] < ent[0]:           # empty conjunction -> zero measure
                ent[1] = ent[0]

    for p in query.predicates:
        if isinstance(p, Range):
            add(p.column if p.column is not None else 0, p.a, p.b,
                p.column is not None)
        elif isinstance(p, Eq):
            add(p.column if p.column is not None else 0,
                p.value - p.halfwidth, p.value + p.halfwidth,
                p.column is not None, is_eq=True)
        else:
            if p.columns is None:
                for j, (lo_v, hi_v) in enumerate(zip(p.lo, p.hi)):
                    add(j, lo_v, hi_v, False)
            else:
                for c, lo_v, hi_v in zip(p.columns, p.lo, p.hi):
                    add(c, lo_v, hi_v, True)

    # Implicit-target resolution runs BEFORE the group term is appended:
    # "SUM(b) WHERE ... GROUP BY code" has one predicate column even though
    # the executed box gains the code axis.
    tgt = 0
    if query.aggregate in ("sum", "avg"):
        t = query.target
        if t is None:
            if len(intervals) != 1:
                raise ValueError("SUM/AVG needs an explicit target unless "
                                 "exactly one predicate column is given")
        elif isinstance(t, bool):
            raise TypeError("target must be a column name or axis index")
        elif isinstance(t, (int, np.integer)):
            if not 0 <= int(t) < len(intervals):
                raise ValueError(f"target axis {t} out of range for "
                                 f"d={len(intervals)}")
            tgt = int(t)
        else:
            if named is False:
                raise ValueError("a string target needs named predicate "
                                 "columns")
            if t not in intervals:
                named = True
                intervals[t] = [-WIDE, WIDE, False]
                eq_only[t] = False
            tgt = list(intervals).index(t)

    if group_value is not None:
        g = query.group_by
        # the group term is a dictionary-code window, i.e. an Eq term
        add(g.column, group_value - EQ_HALFWIDTH, group_value + EQ_HALFWIDTH,
            True, is_eq=True)

    if named is False:
        keys = sorted(intervals)
        if keys != list(range(len(keys))):
            raise ValueError(f"positional predicate axes must be contiguous "
                             f"from 0, got {keys}")
        items = [(k, intervals[k]) for k in keys]
        cols = None
    else:
        items = list(intervals.items())
        cols = tuple(k for k, _ in items)
    group_axis = None
    if group_value is not None and cols is not None:
        group_axis = cols.index(query.group_by.column)
    return _Compiled(
        slot=slot, query=query, group=group_value, cols=cols,
        lo=[e[0] for _, e in items], hi=[e[1] for _, e in items],
        constrained=[e[2] for _, e in items], op=OP_CODES[query.aggregate],
        tgt=tgt, selector=query.selector,
        all_eq=all(eq_only[k] for k, _ in items), group_axis=group_axis,
        kde_backend=query.kde_backend)


def _reorder(c: _Compiled, new_cols: Tuple[str, ...]) -> _Compiled:
    """Permute a compiled box to a tracked joint's axis order."""
    perm = [c.cols.index(col) for col in new_cols]
    return _Compiled(
        slot=c.slot, query=c.query, group=c.group, cols=new_cols,
        lo=[c.lo[j] for j in perm], hi=[c.hi[j] for j in perm],
        constrained=[c.constrained[j] for j in perm], op=c.op,
        tgt=perm.index(c.tgt), selector=c.selector, all_eq=c.all_eq,
        group_axis=None if c.group_axis is None else perm.index(c.group_axis),
        kde_backend=c.kde_backend)


# --- group plans and synopsis resolution ------------------------------------

@dataclass
class _GroupPlan:
    """Execution plan for one (column tuple, selector) group: the resolved
    synopsis plus everything derivable from it alone — the execution path,
    per-axis bandwidths for the accuracy proxy, and the sample->relation
    scale.  Cached by the engine keyed on the synopsis version so repeated
    flushes against an unchanged reservoir skip re-resolution."""
    syn: KDESynopsis
    kind: str                 # "range1d" | "box" | "qmc"
    h_axes: np.ndarray
    scale: float

    @property
    def x_rows(self) -> jnp.ndarray:
        return self.syn.x[:, None] if self.syn.x.ndim == 1 else self.syn.x


def _make_plan(syn: KDESynopsis) -> _GroupPlan:
    x = syn.x[:, None] if syn.x.ndim == 1 else syn.x
    if syn.H is not None:
        kind = "qmc"
        h_axes = np.sqrt(np.diag(np.asarray(syn.H, np.float64)))
    elif syn.x.ndim == 1:
        kind = "range1d"
        h_axes = np.asarray([float(syn.h)], np.float64)
    else:
        kind = "box"
        h_axes = np.asarray(syn.h_diag(), np.float64)
    return _GroupPlan(syn=syn, kind=kind, h_axes=h_axes,
                      scale=syn.n_source / x.shape[0])


class PlanCache:
    """Version-keyed memo of `_GroupPlan`s, owned by a QueryEngine.  An entry
    whose stored version differs from the reservoir's current version misses
    (add_batch therefore invalidates implicitly, same contract as the
    SynopsisCache underneath)."""

    def __init__(self, metrics: Optional[obs.MetricsRegistry] = None):
        self._entries: Dict[object, Tuple[int, _GroupPlan]] = {}
        self.hits = 0
        self.misses = 0
        # registry mirror (aqp.plan.hits/misses), resolved once
        if metrics is not None:
            self._m_hits = metrics.counter("aqp.plan.hits")
            self._m_misses = metrics.counter("aqp.plan.misses")
        else:
            self._m_hits = self._m_misses = None

    def get(self, key, version: int) -> Optional[_GroupPlan]:
        ent = self._entries.get(key)
        if ent is not None and ent[0] == version:
            self.hits += 1
            if self._m_hits is not None:
                self._m_hits.inc()
            return ent[1]
        self.misses += 1
        if self._m_misses is not None:
            self._m_misses.inc()
        return None

    def put(self, key, version: int, plan: _GroupPlan) -> None:
        self._entries[key] = (version, plan)

    def entries(self) -> List[Tuple[object, int]]:
        """[(key, version)] for every live entry — the checkpoint
        serializer's view (plans rebuild from persisted synopses on
        restore, so only the keys need to be durable)."""
        return [(key, version) for key, (version, _plan)
                in self._entries.items()]

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}


class _StoreResolver:
    """Maps a compiled query to a (group key, plan, version) against a
    TelemetryStore: single columns use the per-column reservoirs, multi-column
    boxes match a tracked joint (exact tuple first, then by column *set*,
    reordering the box to the joint's axis order).

    `key_for` is the cheap half (no synopsis fit) — the admission layer uses
    it to bucket pending queries without forcing a fit at submit time.

    `tier` (a `TieredReservoir` tier index, None for the full sample) rides
    in the group key, so a coarse-tier flush and a full-accuracy flush over
    the same column resolve to distinct plans and synopses.
    """

    def __init__(self, store, selector: str,
                 plans: Optional[PlanCache] = None,
                 tier: Optional[int] = None, backend: str = "jnp"):
        self.store = store
        self.selector = selector
        self.plans = plans
        self.tier = tier
        self.backend = backend

    def key_for(self, c: _Compiled):
        """(group key, reordered compiled, reservoir version) — no fitting."""
        # canonical: "Plugin" and "plugin" must land in ONE group (and one
        # cache entry), not two duplicate jitted passes over the same data
        sel = canonical_selector(c.selector or self.selector)
        if c.cols is None:
            raise ValueError("every query must name a column when running "
                             "against a TelemetryStore")
        if len(c.cols) == 1:
            col = c.cols[0]
            res = self.store.columns.get(col)
            if res is None:
                raise KeyError(f"unknown column {col!r}; "
                               f"have {sorted(self.store.columns)}")
            return (col, sel, _effective_tier(res, self.tier)), c, res.version
        cols = c.cols
        joints = self.store.joints
        if cols not in joints:
            match = next((k for k in joints if set(k) == set(cols)), None)
            if match is not None:
                c = _reorder(c, match)
                cols = match
            else:
                raise KeyError(f"no joint reservoir for columns {cols!r}; "
                               f"call track_joint({cols!r}) before add_batch "
                               f"(have {sorted(joints)})")
        res = joints[cols]
        return (cols, sel, _effective_tier(res, self.tier)), c, res.version

    def plan_for(self, key, version: int) -> _GroupPlan:
        """Fit-or-fetch the group's plan for the given reservoir version."""
        if self.plans is not None:
            plan = self.plans.get(key, version)
            if plan is not None:
                return plan
        col, sel, tier = key
        if isinstance(col, tuple):
            syn = self.store.joint_synopsis(col, sel, tier=tier,
                                            backend=self.backend)
        else:
            syn = self.store.synopsis(col, sel, tier=tier,
                                      backend=self.backend)
        plan = _make_plan(syn)
        if self.plans is not None:
            self.plans.put(key, version, plan)
        return plan

    def __call__(self, c: _Compiled):
        key, c2, version = self.key_for(c)
        return key, c2, self.plan_for(key, version), version

    def density_for(self, key, version: int, plan: _GroupPlan):
        """Fit-or-fetch the sublinear RFF density synopsis for a resolved
        full-H group; returns the fitted `RFFSynopsis` or None (exact pass).

        Fits live in the store's `SynopsisCache` next to the exact synopsis,
        keyed (column#rffD, selector) and invalidated by version like every
        other entry — they also persist through the store checkpoint, so a
        restored process serves warm.  A fit that fails the one-shot probe
        accuracy gate is cached *degraded* (no refit churn) and this returns
        None ever after, with the fallback counted per backend.
        """
        from repro.synopses import RFFSynopsis

        col, sel, tier = key
        syn = plan.syn
        if syn.H is None:
            return None
        n_features = _rff_features()
        ckey = _rff_cache_key(_tier_key(col, tier), n_features)
        cache = getattr(self.store, "cache", None)
        metrics = getattr(self.store, "metrics", None)
        if cache is not None:
            hit = cache.get(ckey, sel, version)
            if hit is not None:
                if hit.degraded and metrics is not None:
                    metrics.counter("aqp.synopsis.fallback",
                                    backend="rff").inc()
                return None if hit.degraded else hit
        x = plan.x_rows
        # the seed is a pure function of the (column, selector) identity so
        # refits after version bumps — and fits on other hosts — draw the
        # same frequencies
        seed = zlib.crc32(repr((ckey, sel)).encode()) & 0x7FFFFFFF
        t_fit = time.perf_counter()
        with obs.span("synopsis.fit", backend="rff", n=int(x.shape[0]),
                      n_features=n_features):
            rff = RFFSynopsis.fit(x, syn.H,
                                  n_features=n_features, seed=seed)
            # one-shot gate: mean relative density error on probe points
            # drawn from the fitted sample itself (where the mass is)
            from .kde import kde_eval_H
            probes = x[:RFF_GATE_PROBES]
            f_exact = np.asarray(kde_eval_H(probes, x, syn.H), np.float64)
            f_rff = np.asarray(rff.eval_batch(probes), np.float64)
            denom = max(float(np.mean(f_exact)), 1e-300)
            rff.probe_rel_err = float(np.mean(np.abs(f_rff - f_exact))
                                      / denom)
            rff.degraded = rff.probe_rel_err > RFF_GATE_TOL
        rff.n_source = syn.n_source
        rff.selector = sel
        if metrics is not None:
            metrics.histogram("aqp.synopsis.fit_us", backend="rff").observe(
                (time.perf_counter() - t_fit) * 1e6)
            if rff.degraded:
                metrics.counter("aqp.synopsis.fallback", backend="rff").inc()
        if cache is not None:
            cache.put(ckey, sel, version, rff)
        return None if rff.degraded else rff

    def try_exact(self, c: _Compiled):
        """Sketch answer for an all-Eq single-column query, when the column
        carries a categorical sketch covering its whole stream; returns
        (estimate, version, path, ci_lo, ci_hi, n_effective) or None (KDE
        fallback).  The path is "exact" for a `CategoricalSketch` (zero CI
        width) and "exact:cm" for the bounded-error `CountMinSketch` (CI
        from the deterministic over-count bound — count-min never
        under-counts, so the interval is one-sided for COUNT); a count-min
        window too wide to enumerate (range_terms -> None) falls back to
        the KDE too."""
        if not c.all_eq or c.cols is None or len(c.cols) != 1:
            return None
        col = c.cols[0]
        sketch = getattr(self.store, "categoricals", {}).get(col)
        res = self.store.columns.get(col)
        if sketch is None or res is None or not sketch.exact_for(res.n_seen):
            return None
        terms = sketch.range_terms(c.lo[0], c.hi[0])
        if terms is None:
            return None
        cnt, sm = terms
        if c.op == OP_COUNT:
            est = float(cnt)
        elif c.op == OP_SUM:
            est = float(sm)
        else:
            est = float(sm / cnt) if cnt > 0 else 0.0
        n_eff = int(sketch.n_rows)
        range_err = getattr(sketch, "range_err", None)
        if range_err is None:
            return est, res.version, sketch.path, est, est, n_eff
        err = range_err(c.lo[0], c.hi[0])
        if err is None:                       # raced the coverage gate
            return None
        cnt_err, sum_pos, sum_neg = err
        if c.op == OP_COUNT:
            ci_lo, ci_hi = max(0.0, est - cnt_err), est
        elif c.op == OP_SUM:
            # over-counted positive codes inflate the sum, over-counted
            # negative codes deflate it: the truth window is asymmetric
            ci_lo, ci_hi = sm - sum_pos, sm + sum_neg
        else:
            if cnt <= 0:
                ci_lo, ci_hi = -float("inf"), float("inf")
            else:
                nums = (sm - sum_pos, sm + sum_neg)
                dens = [d for d in (float(cnt), float(max(0, cnt - cnt_err)))
                        if d > 0]
                ratios = [n / d for n in nums for d in dens]
                ci_lo, ci_hi = min(ratios), max(ratios)
        return est, res.version, sketch.path, ci_lo, ci_hi, n_eff


class _MappingResolver:
    """Resolution against a bare synopsis or a {column(s): synopsis} mapping —
    the legacy-shim execution context (no store, no versions)."""

    def __init__(self, synopses):
        self.synopses = synopses
        self._plans: Dict[int, _GroupPlan] = {}   # keyed on synopsis identity

    def _plan(self, syn: KDESynopsis) -> _GroupPlan:
        plan = self._plans.get(id(syn))
        if plan is None:
            plan = self._plans[id(syn)] = _make_plan(syn)
        return plan

    def __call__(self, c: _Compiled):
        d = len(c.lo)
        if isinstance(self.synopses, KDESynopsis):
            if c.cols is not None:
                noun = "column" if d == 1 else "columns"
                raise ValueError(f"queries name columns but a single synopsis "
                                 f"was given; pass a {{{noun}: synopsis}} "
                                 f"mapping")
            return None, c, self._plan(self.synopses), 0
        if c.cols is None:
            if d == 1:
                raise ValueError("queries must name a column when running "
                                 "against a synopsis mapping")
            raise ValueError("queries must name their columns when running "
                             "against a synopsis mapping")
        key = c.cols[0] if len(c.cols) == 1 else c.cols
        if key not in self.synopses:
            # key=str for the listing: the unified mapping may mix plain
            # column keys with column tuples, which don't sort against
            # each other
            have = sorted(self.synopses, key=str)
            if len(c.cols) == 1:
                raise KeyError(f"no synopsis for column {key!r}; have {have}")
            raise KeyError(f"no joint synopsis for columns {key!r}; "
                           f"have {have}")
        return key, c, self._plan(self.synopses[key]), 0


# --- execution --------------------------------------------------------------

def _rel_width(c: _Compiled, h_axes: np.ndarray) -> float:
    widths = [(hi - lo) / h for lo, hi, k, h
              in zip(c.lo, c.hi, c.constrained, h_axes) if k]
    return float(min(widths)) if widths else float("inf")


# Batch shapes are quantized so a stream of variable-size micro-batch flushes
# reuses a handful of jitted executables instead of compiling per size: small
# batches round up to the next power of two (floor 8), larger ones to the next
# multiple of 64 (<= 63 padded rows, each a copy of the last real row, sliced
# off after the pass — per-row vmapped results are unaffected).
_PAD_STEP = 64


def _pad_count(n: int) -> int:
    if n >= _PAD_STEP:
        return -(-n // _PAD_STEP) * _PAD_STEP
    return max(8, 1 << max(n - 1, 0).bit_length())


def _pad_rows(arr: np.ndarray, m: int) -> np.ndarray:
    pad = m - arr.shape[0]
    if pad <= 0:
        return arr
    return np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)])


def _run_group(key, plan: _GroupPlan, entries: List[_Compiled],
               backend: str, n_qmc: int,
               ci_level: float = DEFAULT_CI_LEVEL,
               metrics: Optional[obs.MetricsRegistry] = None,
               tier: Optional[int] = None,
               kde_backend: str = "auto", rff=None
               ) -> List[Tuple[float, str, float, float, int]]:
    """Answer one resolved group in batched passes; returns one
    (estimate, path label, ci_lo, ci_hi, n_effective) per entry, in entry
    order.  The CI comes from a SEPARATE moments pass (aqp_ci) so the
    estimate kernels — and therefore the estimates — stay bit-identical to
    the pre-CI engine.

    GROUP BY families — entries expanded from one query that differ only on
    the group column's code window — are peeled off onto the factored grouped
    kernel (shared box terms evaluated once per flush) when the group runs the
    diagonal-bandwidth box path.
    """
    syn = plan.syn
    x = plan.x_rows
    d_syn = x.shape[1]
    for c in entries:
        if len(c.lo) != d_syn:
            if len(c.lo) == 1:
                raise ValueError(
                    "multi-dimensional synopses answer box predicates, "
                    "not scalar ranges; add one term per axis (legacy: "
                    "BoxQueryBatch, repro.core.aqp_multid)")
            raise ValueError(f"synopsis for {key} is {d_syn}-d but its "
                             f"queries are {len(c.lo)}-d boxes")
    scale = jnp.float32(plan.scale)

    families: List[List[_Compiled]] = []
    rest: List[_Compiled] = []
    if plan.kind == "box":
        by_query: Dict[int, List[_Compiled]] = {}
        for c in entries:
            if (c.group is not None and c.group_axis is not None):
                by_query.setdefault(id(c.query), []).append(c)
            else:
                rest.append(c)
        for fam in by_query.values():
            if len(fam) >= 2:
                families.append(fam)
            else:
                rest.extend(fam)
    else:
        rest = list(entries)

    n_eff = int(x.shape[0])
    p = 0.5 + ci_level / 2.0

    # Instrumentation below (spans, fences, histograms) only fires with
    # `repro.obs` enabled: the NOOP span costs one call, `obs.fence` returns
    # immediately, and the kernel invocations themselves are untouched — so
    # disabled-mode execution stays bit-identical with no extra jit traces
    # (both test-enforced).  Fencing inside the kernel/CI spans makes their
    # durations device-true instead of async-dispatch artifacts.  In
    # profiler mode nothing fences: kernel and CI spans time host
    # preparation and dispatch, and `engine.fetch` the host's wait for the
    # device.
    enabled = obs.enabled()

    # Full-H entries whose resolved density backend is the fitted sublinear
    # synopsis peel off onto the RFF quasi-MC driver; everything else —
    # including every entry when the fit is missing or gated off (rff=None)
    # — continues through the UNTOUCHED legacy pass, so `kde_backend="exact"`
    # answers stay bit-identical to the pre-backend engine.
    rff_entries: List[_Compiled] = []
    if plan.kind == "qmc" and rff is not None:
        still_exact: List[_Compiled] = []
        for c in rest:
            if _resolve_kde_backend(c.kde_backend, kde_backend,
                                    n_eff) == "rff":
                rff_entries.append(c)
            else:
                still_exact.append(c)
        rest = still_exact

    out: Dict[int, Tuple[float, str, float, float, int]] = {}
    if rest:
        n = len(rest)
        m = _pad_count(n)
        t_grp = time.perf_counter() if enabled else 0.0
        ops_np = _pad_rows(np.asarray([c.op for c in rest], np.int32), m)
        if plan.kind == "qmc":
            lo = _pad_rows(np.asarray([c.lo for c in rest], np.float64), m)
            hi = _pad_rows(np.asarray([c.hi for c in rest], np.float64), m)
            tgt = _pad_rows(np.asarray([c.tgt for c in rest], np.int32), m)
            if metrics is not None:
                metrics.counter("aqp.synopsis.hits", backend="exact").inc(n)
            path = "qmc" if backend == "jnp" else f"qmc:{backend}"
            with obs.span("engine.kernel", path=path, n=n, tier=tier):
                ans = batch_query_qmc(x, syn.H, lo, hi, tgt, ops_np, scale,
                                      n_qmc=n_qmc, backend=backend)
                obs.fence(ans)
            with obs.span("engine.ci", path=path, n=n):
                se, dof = qmc_subsample_se(x, syn.H, lo, hi, tgt, ops_np,
                                           syn.n_source, n_qmc)
                obs.fence(se)
            q_ci = t_ppf(p, dof)
        elif plan.kind == "range1d":
            a = _pad_rows(np.asarray([c.lo[0] for c in rest], np.float32), m)
            b = _pad_rows(np.asarray([c.hi[0] for c in rest], np.float32), m)
            path = "range1d" if backend == "jnp" else f"range1d:{backend}"
            with obs.span("engine.kernel", path=path, n=n, tier=tier):
                ans = batch_query_1d(syn.x, syn.h, jnp.asarray(a),
                                     jnp.asarray(b), jnp.asarray(ops_np),
                                     scale, backend=backend)
                obs.fence(ans)
            with obs.span("engine.ci", path=path, n=n):
                mom = moments_1d(syn.x, syn.h, jnp.asarray(a), jnp.asarray(b))
                se = se_from_moments(ops_np, mom, plan.scale, n_eff)
                obs.fence(se)
            q_ci = norm_ppf(p)
        else:
            lo = _pad_rows(np.asarray([c.lo for c in rest], np.float32), m)
            hi = _pad_rows(np.asarray([c.hi for c in rest], np.float32), m)
            tgt = _pad_rows(np.asarray([c.tgt for c in rest], np.int32), m)
            path = "box" if backend == "jnp" else f"box:{backend}"
            with obs.span("engine.kernel", path=path, n=n, tier=tier):
                ans = batch_query_box(x, syn.h_diag(), jnp.asarray(lo),
                                      jnp.asarray(hi), jnp.asarray(tgt),
                                      jnp.asarray(ops_np), scale,
                                      backend=backend)
                obs.fence(ans)
            with obs.span("engine.ci", path=path, n=n):
                mom = moments_box(x, syn.h_diag(), jnp.asarray(lo),
                                  jnp.asarray(hi), jnp.asarray(tgt))
                se = se_from_moments(ops_np, mom, plan.scale, n_eff)
                obs.fence(se)
            q_ci = norm_ppf(p)
        with obs.span("engine.fetch", n=n):
            ans_np = np.asarray(ans, np.float64)[:n]
            se_np = np.asarray(se, np.float64)[:n]
        if enabled and metrics is not None:
            metrics.histogram("aqp.query.latency_us", path=path,
                              tier=tier).observe(
                (time.perf_counter() - t_grp) * 1e6)
        for c, est, s in zip(rest, ans_np, se_np):
            est = float(est)
            out[id(c)] = (est, path, est - q_ci * s, est + q_ci * s, n_eff)

    if rff_entries:
        n = len(rff_entries)
        m = _pad_count(n)
        t_grp = time.perf_counter() if enabled else 0.0
        ops_np = _pad_rows(np.asarray([c.op for c in rff_entries], np.int32),
                           m)
        lo = _pad_rows(np.asarray([c.lo for c in rff_entries], np.float64), m)
        hi = _pad_rows(np.asarray([c.hi for c in rff_entries], np.float64), m)
        tgt = _pad_rows(np.asarray([c.tgt for c in rff_entries], np.int32), m)
        if metrics is not None:
            metrics.counter("aqp.synopsis.hits", backend="rff").inc(n)
        with obs.span("synopsis.eval", backend="rff", n=n,
                      n_features=rff.n_features):
            with obs.span("engine.kernel", path="qmc:rff", n=n, tier=tier):
                ans = batch_query_qmc_rff(x, syn.H, rff, lo, hi, tgt, ops_np,
                                          scale, n_qmc=n_qmc)
                obs.fence(ans)
        # feature-block batch-means SE (O(m*D)) — the sample-chunk subsample
        # CI of the exact path would cost the O(n) pass this backend avoids
        with obs.span("engine.ci", path="qmc:rff", n=n):
            se, dof = qmc_rff_se(rff, x, syn.H, lo, hi, tgt, ops_np,
                                 syn.n_source, n_qmc)
            obs.fence(se)
        q_ci = t_ppf(p, dof)
        with obs.span("engine.fetch", n=n):
            ans_np = np.asarray(ans, np.float64)[:n]
            se_np = np.asarray(se, np.float64)[:n]
        if enabled and metrics is not None:
            lat = (time.perf_counter() - t_grp) * 1e6
            metrics.histogram("aqp.query.latency_us", path="qmc:rff",
                              tier=tier).observe(lat)
            metrics.histogram("aqp.synopsis.eval_us",
                              backend="rff").observe(lat)
        for c, est, s in zip(rff_entries, ans_np, se_np):
            est = float(est)
            out[id(c)] = (est, "qmc:rff",
                          est - q_ci * s, est + q_ci * s, n_eff)

    fam_path = ("box:grouped" if backend == "jnp"
                else f"box:grouped:{backend}")
    for fam in families:
        g_axis = fam[0].group_axis
        gm = _pad_count(len(fam))
        t_grp = time.perf_counter() if enabled else 0.0
        glo = _pad_rows(np.asarray([c.lo[g_axis] for c in fam], np.float32),
                        gm)
        ghi = _pad_rows(np.asarray([c.hi[g_axis] for c in fam], np.float32),
                        gm)
        with obs.span("engine.kernel", path=fam_path, n=len(fam),
                      tier=tier):
            ans = batch_query_box_grouped(
                x, syn.h_diag(), fam[0].lo, fam[0].hi, glo, ghi,
                g_axis=g_axis, tgt=fam[0].tgt, op=fam[0].op, scale=scale,
                backend=backend)
            obs.fence(ans)
        with obs.span("engine.fetch", n=len(fam)):
            ans_np = np.asarray(ans, np.float64)[:len(fam)]
        # family moments run on the per-entry FULL boxes (each entry's box
        # already carries its group window from _compile)
        flo = _pad_rows(np.asarray([c.lo for c in fam], np.float32), gm)
        fhi = _pad_rows(np.asarray([c.hi for c in fam], np.float32), gm)
        ftgt = _pad_rows(np.asarray([c.tgt for c in fam], np.int32), gm)
        fops = np.full(gm, fam[0].op, np.int32)
        with obs.span("engine.ci", path=fam_path, n=len(fam)):
            mom = moments_box(x, syn.h_diag(), jnp.asarray(flo),
                              jnp.asarray(fhi), jnp.asarray(ftgt))
            se = se_from_moments(fops, mom, plan.scale, n_eff)
            obs.fence(se)
        with obs.span("engine.fetch", n=len(fam)):
            se_np = np.asarray(se, np.float64)[:len(fam)]
        if enabled and metrics is not None:
            metrics.histogram("aqp.query.latency_us", path=fam_path,
                              tier=tier).observe(
                (time.perf_counter() - t_grp) * 1e6)
        q_ci = norm_ppf(p)
        for c, est, s in zip(fam, ans_np, se_np):
            est = float(est)
            out[id(c)] = (est, fam_path,
                          est - q_ci * s, est + q_ci * s, n_eff)

    return [out[id(c)] for c in entries]


def _execute(compiled: Sequence[_Compiled], n_out: int, resolver,
             backend: str = "jnp", n_qmc: int = 4096,
             ci_level: float = DEFAULT_CI_LEVEL,
             kde_backend: str = "auto") -> List[AqpResult]:
    """Answer compiled queries: exact categorical sketches first (when the
    resolver offers them), then group the rest by resolved synopsis, answer
    each group in batched passes on its execution path, and scatter back to
    submission order."""
    results: List[Optional[AqpResult]] = [None] * n_out
    try_exact = getattr(resolver, "try_exact", None)
    remaining: List[_Compiled] = []
    with obs.span("engine.exact", n=len(compiled)):
        for c in compiled:
            hit = try_exact(c) if try_exact is not None else None
            if hit is not None:
                est, version, path, ci_lo, ci_hi, n_eff = hit
                # rel_width=0.0: an exact answer has NO smoothing — the proxy
                # must rank it best, not worst (inf is reserved for genuinely
                # unconstrained estimates)
                results[c.slot] = AqpResult(
                    estimate=est, path=path, rel_width=0.0,
                    synopsis_version=version, group=c.group, query=c.query,
                    ci_lo=ci_lo, ci_hi=ci_hi, ci_level=ci_level,
                    n_effective=n_eff)
            else:
                remaining.append(c)

    # store-backed resolvers expose the owning store's registry and their
    # tier budget; the mapping resolver (execute_specs) has neither
    metrics = getattr(getattr(resolver, "store", None), "metrics", None)
    tier = getattr(resolver, "tier", None)

    groups: "Dict[object, dict]" = {}
    with obs.span("engine.plan", n=len(remaining), tier=tier):
        for c in remaining:
            key, c2, plan, version = resolver(c)
            g = groups.setdefault(key, {"plan": plan, "version": version,
                                        "entries": []})
            g["entries"].append(c2)

    for key, g in groups.items():
        plan: _GroupPlan = g["plan"]
        entries: List[_Compiled] = g["entries"]
        rff = None
        if plan.kind == "qmc":
            # fit-or-fetch the sublinear synopsis only when some entry's
            # resolved backend wants it (and the resolver is store-backed:
            # the fit cache and the accuracy-gate counters live there)
            n_rows = int(plan.x_rows.shape[0])
            density_for = getattr(resolver, "density_for", None)
            if density_for is not None and any(
                    _resolve_kde_backend(c.kde_backend, kde_backend,
                                         n_rows) == "rff"
                    for c in entries):
                rff = density_for(key, g["version"], plan)
        answered = _run_group(key, plan, entries, backend, n_qmc,
                              ci_level=ci_level, metrics=metrics, tier=tier,
                              kde_backend=kde_backend, rff=rff)
        for c, (est, path, ci_lo, ci_hi, n_eff) in zip(entries, answered):
            results[c.slot] = AqpResult(
                estimate=est, path=path,
                rel_width=_rel_width(c, plan.h_axes),
                synopsis_version=g["version"], group=c.group, query=c.query,
                ci_lo=ci_lo, ci_hi=ci_hi, ci_level=ci_level,
                n_effective=n_eff)
    return results


# --- the facade -------------------------------------------------------------

class QueryEngine:
    """Single entry point for AQP batches against a `TelemetryStore`.

    A heterogeneous batch — 1-D ranges, multi-d boxes, categorical equality,
    GROUP BY expansions, mixed selectors — is normalized, grouped by
    (column tuple, selector), and each group is answered in one batched call
    on its execution path (closed forms, eq. 11 product kernel, the Pallas
    tile kernels, or the batched quasi-MC fallback for full-H synopses).

        engine = QueryEngine(store)                # or store.engine()
        results = engine.execute([
            AqpQuery("count", (Range("loss", 1.0, 4.0),)),
            AqpQuery("avg", (Box(("loss", "latency_ms"), (1, 20), (4, 60)),),
                     target="latency_ms"),
            AqpQuery("count", (Eq("model_id", 2),)),
        ])
    """

    def __init__(self, store, selector: str = "plugin", backend: str = "jnp",
                 n_qmc: int = 4096, max_groups: int = 64,
                 ci_level: float = DEFAULT_CI_LEVEL,
                 kde_backend: str = "auto"):
        if kde_backend not in KDE_BACKENDS:
            raise ValueError(f"unknown kde_backend {kde_backend!r}; "
                             f"expected one of {KDE_BACKENDS}")
        self.store = store
        self.selector = selector
        self.backend = backend
        self.n_qmc = n_qmc
        self.max_groups = max_groups
        self.ci_level = ci_level
        self.kde_backend = kde_backend
        self.plans = PlanCache(metrics=getattr(store, "metrics", None))

    # -- planning core (shared by the synchronous path and the admission
    #    layer in repro.core.aqp_admission) ----------------------------------

    def compile(self, queries: Union[AqpQuery, Sequence[AqpQuery]]
                ) -> List[_Compiled]:
        """Normalize specs to execution units (one per GROUP BY category),
        slotted in submission order."""
        if isinstance(queries, AqpQuery):
            queries = [queries]
        compiled: List[_Compiled] = []
        for q in queries:
            if not isinstance(q, AqpQuery):
                raise TypeError(f"QueryEngine.execute takes AqpQuery specs, "
                                f"got {type(q).__name__}")
            for gv in self._group_values(q):
                compiled.append(_compile(q, len(compiled), group_value=gv))
        return compiled

    def resolver(self, selector: Optional[str] = None,
                 tier: Optional[int] = None,
                 backend: Optional[str] = None) -> _StoreResolver:
        """Store resolver wired to this engine's version-keyed plan cache.
        `tier` budgets resolution to one tier of a `TieredReservoir` (None =
        the full sample; plain reservoirs ignore it); `backend` (default the
        engine's) runs the PLUGIN fits it triggers."""
        return _StoreResolver(self.store, selector or self.selector,
                              plans=self.plans, tier=tier,
                              backend=backend or self.backend)

    def run_compiled(self, compiled: Sequence[_Compiled],
                     selector: Optional[str] = None,
                     backend: Optional[str] = None,
                     tier: Optional[int] = None,
                     kde_backend: Optional[str] = None) -> List[AqpResult]:
        """Execute pre-compiled units (slots must be 0..n-1) — the admission
        layer's flush entry point; identical execution to `execute`."""
        with obs.span("engine.run_compiled", n=len(compiled), tier=tier,
                      backend=backend or self.backend):
            return _execute(compiled, len(compiled),
                            self.resolver(selector, tier=tier,
                                          backend=backend),
                            backend=backend or self.backend, n_qmc=self.n_qmc,
                            ci_level=self.ci_level,
                            kde_backend=kde_backend or self.kde_backend)

    # -- the synchronous shell ----------------------------------------------

    def execute(self, queries: Union[AqpQuery, Sequence[AqpQuery]],
                selector: Optional[str] = None,
                backend: Optional[str] = None, mode: str = "batch",
                kde_backend: Optional[str] = None):
        """Answer a batch of AqpQuery specs; one AqpResult per query (one per
        group value for GROUP BY queries, in discovered/declared order).

        `mode="batch"` (default) returns the List[AqpResult] directly;
        `mode="progressive"` returns the `progressive` generator instead —
        (tier, results) rounds with tightening confidence intervals.

        `kde_backend` overrides the engine's density-backend default for
        this batch ("auto" | "exact" | "rff", quasi-MC path only)."""
        if mode == "progressive":
            return self.progressive(queries, selector=selector,
                                    backend=backend)
        if mode != "batch":
            raise ValueError(f"unknown mode {mode!r}; "
                             f"expected 'batch' or 'progressive'")
        return self.run_compiled(self.compile(queries), selector=selector,
                                 backend=backend, kde_backend=kde_backend)

    def progressive(self, queries: Union[AqpQuery, Sequence[AqpQuery]],
                    selector: Optional[str] = None,
                    backend: Optional[str] = None):
        """Anytime execution over `TieredReservoir` tiers: yields
        (tier, List[AqpResult]) rounds, answering from the smallest tier
        first and refining on successively larger tiers.  The final round
        runs on the full sample and is bit-identical to `execute` — callers
        can stop consuming as soon as the intervals are tight enough.
        Against stores with no tiered reservoirs this degenerates to one
        full-accuracy round."""
        compiled = self.compile(queries)
        res = self.resolver(selector)
        n_tiers = 1
        for c in compiled:
            key, _c2, _version = res.key_for(c)
            col = key[0]
            reg = self.store.joints if isinstance(col, tuple) \
                else self.store.columns
            n_tiers = max(n_tiers, getattr(reg.get(col), "n_tiers", 1))
        for t in range(n_tiers):
            tier = t if t < n_tiers - 1 else None
            yield t, self.run_compiled(compiled, selector=selector,
                                       backend=backend, tier=tier)

    def answers(self, queries, **kw) -> np.ndarray:
        """`execute`, reduced to the estimates (submission order)."""
        return np.asarray([r.estimate for r in self.execute(queries, **kw)],
                          np.float64)

    def session(self, **kwargs) -> "AqpSession":
        """A streaming admission session over this engine: submit AqpQuery
        specs from many logical clients, get futures back, micro-batches
        flush on a batch-size watermark or max-delay deadline (see
        repro.core.aqp_admission)."""
        from .aqp_admission import AqpSession
        return AqpSession(self, **kwargs)

    def _group_values(self, q: AqpQuery) -> List[Optional[float]]:
        if q.group_by is None:
            return [None]
        gb = q.group_by
        if gb.values is not None:
            return list(gb.values)
        res = self.store.columns.get(gb.column)
        if res is None:
            raise KeyError(f"unknown group_by column {gb.column!r}; "
                           f"have {sorted(self.store.columns)}")
        codes = np.unique(np.round(res.sample().astype(np.float64)))
        strata = getattr(res, "codes", None)
        if callable(strata):
            # stratified TieredReservoir: union in codes whose last uniform
            # representative was displaced — rare groups keep a result row
            codes = np.unique(np.concatenate(
                [codes, np.round(np.asarray(strata(), np.float64))]))
        if codes.size == 0:
            raise ValueError(f"group_by column {gb.column!r} has no data")
        if codes.size > self.max_groups:
            raise ValueError(
                f"group_by {gb.column!r} has {codes.size} distinct codes "
                f"(max_groups={self.max_groups}); pass "
                f"GroupBy({gb.column!r}, values=...) to pin the categories")
        return [float(v) for v in codes]


# --- legacy bridges (QueryBatch / BoxQueryBatch shims) ----------------------

def from_query(q) -> AqpQuery:
    """Compile a legacy 1-D `Query` to an AqpQuery spec."""
    return AqpQuery(q.op, (Range(q.column, q.a, q.b),))


def from_box_query(q) -> AqpQuery:
    """Compile a legacy `BoxQuery` to an AqpQuery spec."""
    target = None if q.op == "count" else q.target_index()
    return AqpQuery(q.op, (Box(q.columns, q.lo, q.hi),), target=target)


def execute_specs(specs: Sequence[AqpQuery], synopses,
                  backend: str = "jnp", n_qmc: int = 4096) -> np.ndarray:
    """Execute AqpQuery specs against a bare synopsis or a mapping (the
    legacy-shim context); returns estimates in submission order.

    GROUP BY expansion and per-query selector overrides need a store (the
    category discovery and the re-fit both live there), so specs carrying
    them are rejected here rather than silently half-executed.
    """
    for q in specs:
        if q.group_by is not None:
            raise ValueError("group_by needs a store-backed QueryEngine; "
                             "execute_specs runs against pre-fitted synopses")
        if q.selector is not None:
            raise ValueError("a per-query selector override needs a "
                             "store-backed QueryEngine; execute_specs runs "
                             "against pre-fitted synopses")
    compiled = [_compile(q, i) for i, q in enumerate(specs)]
    res = _execute(compiled, len(compiled), _MappingResolver(synopses),
                   backend=backend, n_qmc=n_qmc)
    return np.asarray([r.estimate for r in res], np.float64)
