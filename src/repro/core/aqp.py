"""Approximate query processing on KDE synopses (paper §4.3, eqs. 9-11).

A `KDESynopsis` replaces a column (or a small set of columns) of a relation:
  COUNT(a<=X<=b)  ~= n * Integral_a^b f^(x) dx                  (eq. 9)
  SUM(X; a..b)    ~= n * Integral_a^b x f^(x) dx                (eq. 10)
  AVG             = SUM / COUNT                                 (§4.3)

For the Gaussian kernel both 1-D integrals have closed forms, which we use
instead of the generic quadrature the paper mentions (§4.1(b)) — an exactness
*and* speed win recorded in DESIGN.md §2:

  Integral_a^b K_h(x - Xi) dx           = Phi((b-Xi)/h) - Phi((a-Xi)/h)
  Integral_a^b x K_h(x - Xi) dx         = Xi [Phi(.)]_a^b - h [phi((x-Xi)/h)]_a^b

Multi-d axis-aligned boxes: product of per-axis Phi terms for scalar/diagonal
bandwidths (eq. 11); full-H synopses fall back to deterministic quasi-MC.
Synopses are *mergeable* (weighted union of sample points) so they can be
folded across hosts of a training fleet — the scale-out behaviour the paper's
single-node design lacks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .kde import kde_eval, silverman_h
from .lscv import collapse, lscv_H, lscv_h
from .plugin import plugin_bandwidth

SQRT1_2 = 1.0 / math.sqrt(2.0)


def canonical_selector(selector: str) -> str:
    """Case-normalized bandwidth-selector name, used for cache keys and
    engine group keys.

    "Plugin"/"PLUGIN"/"plugin" must resolve to ONE selector (two live cache
    copies of the same synopsis waste the byte budget and can serve a stale
    copy after the other refits).  The one legitimate case pair is the
    paper's scalar vs full-matrix LSCV — "lscv_h" and "lscv_H" are
    *different* selectors and stay distinct.
    """
    low = selector.lower()
    if low == "lscv_h" and selector.endswith("H"):
        return "lscv_H"
    return low


def _Phi(z):
    return 0.5 * (1.0 + jax.scipy.special.erf(z * SQRT1_2))


def _phi(z):
    return jnp.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)


@jax.jit
def count_1d(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """eq. (9), closed form: n * mean_i [Phi((b-Xi)/h) - Phi((a-Xi)/h)]."""
    n = x.shape[0]
    za = (a - x) / h
    zb = (b - x) / h
    return n * jnp.mean(_Phi(zb) - _Phi(za))


@jax.jit
def sum_1d(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """eq. (10), closed form for the Gaussian kernel."""
    za = (a - x) / h
    zb = (b - x) / h
    term_mu = x * (_Phi(zb) - _Phi(za))
    term_h = -h * (_phi(zb) - _phi(za))
    return jnp.sum(term_mu + term_h)


@partial(jax.jit, static_argnames=("n_grid",))
def count_1d_numeric(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array,
                     n_grid: int = 513) -> jax.Array:
    """eq. (9) by trapezoid quadrature — the generic path the paper describes;
    kept as a cross-check oracle against the closed form."""
    n = x.shape[0]
    grid = jnp.linspace(a, b, n_grid)
    f = kde_eval(grid, x, h)
    return n * jnp.trapezoid(f, grid)


@partial(jax.jit, static_argnames=("n_grid",))
def sum_1d_numeric(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array,
                   n_grid: int = 513) -> jax.Array:
    n = x.shape[0]
    grid = jnp.linspace(a, b, n_grid)
    f = kde_eval(grid, x, h)
    return n * jnp.trapezoid(grid * f, grid)


@jax.jit
def count_box_diag(x: jax.Array, h_diag: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """eq. (11) for axis-aligned boxes with scalar/diagonal bandwidth:
    product kernel => per-axis Phi factors.  x: (n,d), h_diag: (d,)."""
    n = x.shape[0]
    za = (lo[None, :] - x) / h_diag[None, :]
    zb = (hi[None, :] - x) / h_diag[None, :]
    per_axis = _Phi(zb) - _Phi(za)            # (n, d)
    return n * jnp.mean(jnp.prod(per_axis, axis=1))


@jax.jit
def sum_box_diag(x: jax.Array, h_diag: jax.Array, lo: jax.Array, hi: jax.Array,
                 target: jax.Array) -> jax.Array:
    """SUM of axis `target` over an axis-aligned box (eq. 11 x eq. 10):
    the product kernel factorises, so the box integral of x_t f^(x) is the
    per-axis Phi-difference product with axis t's factor replaced by the 1-D
    first-moment closed form  X_it [Phi]_a^b - h_t [phi]_a^b.
    x: (n,d), h_diag: (d,), target: scalar int axis index."""
    za = (lo[None, :] - x) / h_diag[None, :]
    zb = (hi[None, :] - x) / h_diag[None, :]
    d_Phi = _Phi(zb) - _Phi(za)                               # (n, d)
    moment = x * d_Phi - h_diag[None, :] * (_phi(zb) - _phi(za))
    axis = jnp.arange(x.shape[1])
    factors = jnp.where(axis[None, :] == target, moment, d_Phi)
    return jnp.sum(jnp.prod(factors, axis=1))


def _halton(n: int, d: int) -> jnp.ndarray:
    """Deterministic quasi-MC nodes (for full-H boxes)."""
    import numpy as np
    primes = [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37][:d]
    out = np.zeros((n, d))
    for k, p in enumerate(primes):
        i = np.arange(1, n + 1)
        f = np.zeros(n)
        denom = 1.0
        rem = i.astype(np.float64)
        base = np.zeros(n)
        denom = p
        while rem.max() > 0:
            base += (rem % p) / denom
            rem = rem // p
            denom *= p
        out[:, k] = base
    return jnp.asarray(out, jnp.float32)


def box_qmc_terms(x: jax.Array, H: jax.Array, lo: jax.Array, hi: jax.Array,
                  target: int = 0, n_qmc: int = 4096):
    """Full-matrix-H box integrals by deterministic quasi-MC, returning
    (count, sum_target) from ONE density evaluation — the Halton nodes and
    the O(n_qmc * sample) kde_eval_H pass are the whole cost, and COUNT and
    SUM share both:  count = n vol mean(f),  sum = n vol mean(node_t f)."""
    from .kde import kde_eval_H
    n, d = x.shape
    nodes = lo[None, :] + _halton(n_qmc, d) * (hi - lo)[None, :]
    f = kde_eval_H(nodes, x, H)
    vol = jnp.prod(hi - lo)
    return n * vol * jnp.mean(f), n * vol * jnp.mean(nodes[:, target] * f)


def count_box_H(x: jax.Array, H: jax.Array, lo: jax.Array, hi: jax.Array,
                n_qmc: int = 4096) -> jax.Array:
    """Full-matrix-H COUNT over a box via quasi-Monte-Carlo on the box."""
    return box_qmc_terms(x, H, lo, hi, n_qmc=n_qmc)[0]


def sum_box_H(x: jax.Array, H: jax.Array, lo: jax.Array, hi: jax.Array,
              target: int = 0, n_qmc: int = 4096) -> jax.Array:
    """Full-matrix-H SUM of axis `target` over a box (quasi-MC)."""
    return box_qmc_terms(x, H, lo, hi, target=target, n_qmc=n_qmc)[1]


@dataclass
class KDESynopsis:
    """A fitted density synopsis for one numeric column (or column set).

    `h` is a scalar bandwidth for 1-D synopses, and may be a (d,) diagonal
    bandwidth vector for multi-d synopses (per-axis PLUGIN / silverman /
    LSCV_h — the product-kernel form of eq. 11).  `H` is the full bandwidth
    matrix (LSCV_H); exactly one of `h`/`H` is set.  An LSCV_h synopsis also
    keeps what its bandwidth is made of, per axis: the grid minimiser
    `grid_h` of g(h) (eq. 24, for H = h^2 sigma^2) and the sample standard
    deviation `sigma` (eq. 22), with h = grid_h * sigma; and `grid_rows`,
    the points each axis's grid sums ran over: the sample's n, or u_p where
    they ran over the axis's distinct values (`core.lscv.collapse`).
    """
    x: jax.Array                  # retained sample (the synopsis payload)
    h: Optional[jax.Array] = None # scalar or (d,) diagonal bandwidth
    H: Optional[jax.Array] = None # full bandwidth matrix (LSCV_H)
    n_source: int = 0             # size of the original relation
    selector: str = "plugin"
    grid_h: Optional[jax.Array] = None  # LSCV_h grid minimiser(s)
    sigma: Optional[jax.Array] = None   # LSCV_h sample std deviation(s)
    grid_rows: Optional[Tuple[int, ...]] = None  # LSCV_h points per axis

    @classmethod
    def fit(cls, data: jax.Array, selector: str = "plugin", max_sample: int = 4096,
            seed: int = 0, backend: str = "jnp") -> "KDESynopsis":
        host = data if isinstance(data, np.ndarray) else None
        data = jnp.asarray(data, jnp.float32)
        n_source = data.shape[0]
        if n_source > max_sample:   # numerosity reduction (paper §2.1)
            idx = jax.random.permutation(jax.random.PRNGKey(seed), n_source)[:max_sample]
            sample, host = data[idx], None
        else:
            sample = data
        if selector == "plugin":
            if sample.ndim == 1:
                h = plugin_bandwidth(sample, backend=backend).h
            else:
                # per-axis PLUGIN: the paper's selector is univariate (§4.4),
                # so the multi-d product kernel takes one PLUGIN h per axis
                h = jnp.stack([plugin_bandwidth(sample[:, j], backend=backend).h
                               for j in range(sample.shape[1])])
            return cls(x=sample, h=h, n_source=n_source, selector=selector)
        if selector == "silverman":
            if sample.ndim == 1:
                h = silverman_h(sample)
            else:
                h = jnp.stack([silverman_h(sample[:, j])
                               for j in range(sample.shape[1])])
            return cls(x=sample, h=h, n_source=n_source, selector=selector)
        if selector == "lscv_h":
            # LSCV_h picks h for H = h^2 Sigma; the product kernel takes a
            # diagonal, so each axis gets its own 1-D LSCV_h (as PLUGIN
            # does) and bandwidth h_j sigma_j
            axes = [sample] if sample.ndim == 1 else \
                [sample[:, j] for j in range(sample.shape[1])]
            # a tied axis sums its pairs over its distinct values (the
            # Pallas kernel's weighted variant), where that is cheaper
            ties = [None] * len(axes)
            if backend == "pallas":
                cols = np.asarray(sample if host is None else host,
                                  np.float32).reshape(len(sample), -1)
                ties = [collapse(c) for c in cols.T]
            fits = [lscv_h(a, backend=backend, ties=t)
                    for a, t in zip(axes, ties)]
            grid_h = jnp.stack([f.h for f in fits])
            sigma = jnp.sqrt(jnp.stack([f.sigma[0, 0] for f in fits]))
            if sample.ndim == 1:
                grid_h, sigma = grid_h[0], sigma[0]
            rows = tuple(len(sample) if t is None else t.values.size
                         for t in ties)
            return cls(x=sample, h=grid_h * sigma, n_source=n_source,
                       selector=selector, grid_h=grid_h, sigma=sigma,
                       grid_rows=rows)
        if selector == "lscv_H":
            res = lscv_H(sample if sample.ndim == 2 else sample[:, None])
            return cls(x=sample, H=res.H, n_source=n_source, selector=selector)
        raise ValueError(f"unknown selector {selector!r}")

    # --- queries ----------------------------------------------------------
    def _scale(self) -> float:
        """Scale factor from retained sample to the full relation."""
        return self.n_source / self.x.shape[0]

    def count(self, a: float, b: float) -> jax.Array:
        if self.x.ndim == 1:
            return self._scale() * count_1d(self.x, self.h, jnp.float32(a), jnp.float32(b))
        raise ValueError("use count_box for multi-d synopses")

    def sum(self, a: float, b: float) -> jax.Array:
        if self.x.ndim == 1:
            return self._scale() * sum_1d(self.x, self.h, jnp.float32(a), jnp.float32(b))
        raise ValueError("1-D only")

    def avg(self, a: float, b: float) -> jax.Array:
        return _avg_or_zero(self.count(a, b), self.sum(a, b))

    def _as_rows(self) -> jax.Array:
        return self.x[:, None] if self.x.ndim == 1 else self.x

    def h_diag(self) -> jax.Array:
        """Per-axis bandwidth vector (scalar h broadcast to every axis)."""
        d = self._as_rows().shape[1]
        return jnp.broadcast_to(jnp.asarray(self.h, jnp.float32), (d,))

    def _target_index(self, target) -> int:
        d = self._as_rows().shape[1]
        t = 0 if target is None else int(target)
        if not 0 <= t < d:
            raise ValueError(f"target axis {t} out of range for d={d}")
        return t

    def count_box(self, lo, hi) -> jax.Array:
        x = self._as_rows()
        lo = jnp.asarray(lo, jnp.float32)
        hi = jnp.asarray(hi, jnp.float32)
        if self.H is not None:
            return self._scale() * count_box_H(x, self.H, lo, hi)
        return self._scale() * count_box_diag(x, self.h_diag(), lo, hi)

    def sum_box(self, lo, hi, target: Optional[int] = None) -> jax.Array:
        """SUM of axis `target` (default axis 0) over an axis-aligned box."""
        x = self._as_rows()
        lo = jnp.asarray(lo, jnp.float32)
        hi = jnp.asarray(hi, jnp.float32)
        t = self._target_index(target)
        if self.H is not None:
            return self._scale() * sum_box_H(x, self.H, lo, hi, target=t)
        return self._scale() * sum_box_diag(x, self.h_diag(), lo, hi, jnp.int32(t))

    def avg_box(self, lo, hi, target: Optional[int] = None) -> jax.Array:
        return _avg_or_zero(self.count_box(lo, hi), self.sum_box(lo, hi, target))

    def merge(self, other: "KDESynopsis", max_sample: int = 4096, seed: int = 0) -> "KDESynopsis":
        """Mergeable synopses (beyond paper): union the retained samples
        (subsample if needed) and refit the bandwidth on the merged sample."""
        merged = jnp.concatenate([self.x, other.x], axis=0)
        return KDESynopsis.fit(merged, selector=self.selector, max_sample=max_sample,
                               seed=seed)._replace_source(self.n_source + other.n_source)

    def _replace_source(self, n_source: int) -> "KDESynopsis":
        self.n_source = n_source
        return self

    def query_batch(self, queries: Sequence["Query"], backend: str = "jnp") -> np.ndarray:
        """Answer N COUNT/SUM/AVG range queries in one jitted pass."""
        queries = [q if isinstance(q, Query) else Query(*q) for q in queries]
        return run_legacy_queries(queries, self, backend=backend)

    def query_box_batch(self, queries, backend: str = "jnp") -> np.ndarray:
        """Answer N COUNT/SUM/AVG box queries (eq. 11) in one jitted pass."""
        from .aqp_multid import BoxQuery, run_legacy_boxes
        queries = [q if isinstance(q, BoxQuery) else BoxQuery(*q)
                   for q in queries]
        return run_legacy_boxes(queries, self, backend=backend)


# --- batched query engine -------------------------------------------------
#
# A production AQP front end amortises planning and kernel launches across
# thousands of concurrent queries (cf. Verdict's batch planner).  The closed
# forms of eqs. 9-10 share all their per-sample work — Phi/phi differences —
# so a whole heterogeneous batch against one synopsis reduces to ONE
# (queries x sample) two-channel reduction, then a per-query select.
#
# `Query`/`QueryBatch` are the legacy 1-D surface: `QueryBatch.run` is a
# deprecated shim over the unified declarative engine in aqp_query.py
# (`AqpQuery` + `QueryEngine`), which also routes boxes, categorical Eq
# terms, GROUP BY, and the full-H quasi-MC fallback.

OP_COUNT, OP_SUM, OP_AVG = 0, 1, 2
OP_CODES = {"count": OP_COUNT, "sum": OP_SUM, "avg": OP_AVG}

# COUNT below this is an empty selection for AVG purposes (see _avg_or_zero).
AVG_MIN_COUNT = 1e-3


def _avg_or_zero(counts, sums):
    """AVG = SUM / COUNT, defined as 0 for (effectively) empty selections:
    below the threshold the ratio is 0/0 noise amplified by 1/count.  Both the
    scalar and the batched path route through here so they agree exactly."""
    return jnp.where(counts > AVG_MIN_COUNT,
                     sums / jnp.maximum(counts, 1e-12), 0.0)


@dataclass(frozen=True)
class Query:
    """One aggregate range query: OP(column) WHERE a <= column <= b."""
    op: str                        # "count" | "sum" | "avg"
    a: float
    b: float
    column: Optional[str] = None   # None when run against a single synopsis

    def __post_init__(self):
        if self.op not in OP_CODES:
            raise ValueError(f"unknown op {self.op!r}; expected one of {sorted(OP_CODES)}")


def _batch_terms(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array):
    """vmapped closed forms: per-query unscaled (count_raw, sum_raw)."""
    def one(aq, bq):
        za = (aq - x) / h
        zb = (bq - x) / h
        d_Phi = _Phi(zb) - _Phi(za)
        cnt = jnp.sum(d_Phi)
        # same elementwise association as sum_1d so both paths agree tightly
        sm = jnp.sum(x * d_Phi - h * (_phi(zb) - _phi(za)))
        return cnt, sm
    return jax.vmap(one)(a, b)


@partial(jax.jit, static_argnames=("backend",))
def batch_query_1d(x: jax.Array, h: jax.Array, a: jax.Array, b: jax.Array,
                   ops: jax.Array, scale: jax.Array,
                   backend: str = "jnp") -> jax.Array:
    """Answer a mixed batch against one 1-D synopsis in a single jitted call.

    x: (n,) retained sample; a/b/ops: (q,); scale: sample->relation factor.
    backend="pallas" routes the (queries x sample) reduction through the
    kernels/aqp_batch.py tile kernel.
    """
    if backend == "pallas":
        from repro.kernels import ops as kops
        cnt_raw, sum_raw = kops.aqp_batch_sums(x, h, a, b)
    else:
        cnt_raw, sum_raw = _batch_terms(x, h, a, b)
    counts = scale * cnt_raw
    sums = scale * sum_raw
    avgs = _avg_or_zero(counts, sums)
    return jnp.select([ops == OP_COUNT, ops == OP_SUM], [counts, sums], avgs)


@dataclass
class QueryBatch:
    """Planner for heterogeneous query batches.

    Groups queries by target column so each synopsis is answered in a single
    jitted pass, then scatters results back to submission order.
    """
    queries: Sequence[Query]
    _groups: Dict[Optional[str], List[int]] = field(init=False, repr=False)

    def __post_init__(self):
        self.queries = [q if isinstance(q, Query) else Query(*q) for q in self.queries]
        groups: Dict[Optional[str], List[int]] = {}
        for i, q in enumerate(self.queries):
            groups.setdefault(q.column, []).append(i)
        self._groups = groups

    def __len__(self) -> int:
        return len(self.queries)

    @property
    def columns(self) -> List[Optional[str]]:
        return list(self._groups)

    def plan(self, column: Optional[str]):
        """(indices, a, b, opcodes) device arrays for one column's group."""
        idx = self._groups[column]
        qs = [self.queries[i] for i in idx]
        a = jnp.asarray([q.a for q in qs], jnp.float32)
        b = jnp.asarray([q.b for q in qs], jnp.float32)
        ops_arr = jnp.asarray([OP_CODES[q.op] for q in qs], jnp.int32)
        return idx, a, b, ops_arr

    def run(self, synopses: Union[KDESynopsis, Mapping[str, KDESynopsis]],
            backend: str = "jnp") -> np.ndarray:
        """Deprecated shim: compiles to `AqpQuery` specs and executes through
        the unified engine (repro.core.aqp_query); answers in submission
        order, bit-for-bit identical to `QueryEngine.execute`."""
        import warnings

        warnings.warn(
            "QueryBatch.run is deprecated; build AqpQuery specs and execute "
            "them through repro.core.aqp_query.QueryEngine (or "
            "TelemetryStore.query)", DeprecationWarning, stacklevel=2)
        return run_legacy_queries(self.queries, synopses, backend=backend)


def run_legacy_queries(queries: Sequence[Query], synopses,
                       backend: str = "jnp") -> np.ndarray:
    """Execute legacy 1-D `Query` objects through the unified engine —
    the shim body, shared with `KDESynopsis.query_batch` (which keeps its
    non-deprecated convenience signature)."""
    from .aqp_query import execute_specs, from_query
    return execute_specs([from_query(q) for q in queries], synopses,
                         backend=backend)
