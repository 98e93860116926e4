"""Plain reference for the AQP cells: the same semantics, written apart from
the program (it imports nothing of `repro` and takes nothing it made).

What a deployment's answers mean, as the configuration files state it:

  * a column's (or column tuple's) reservoir is an algorithm-R uniform
    sample of every row ingested, drawn with numpy's default generator from
    the store's seed (`seed + crc32(name) % 1000`; the top tier of a tiered
    ladder adds `n_tiers - 1`; a joint registered after ingest starts from
    the per-column samples zipped row by row).  `Reservoir` replays it from
    the benchmark's own data;
  * the bandwidth of every axis is PLUGIN (paper eqs. 12-19), computed here
    from that sample with the O(n^2) pair sums on the device in blocks, each
    block's partial sum carried to the host and added in float64;
  * a COUNT / SUM / AVG answer is the Gaussian product-kernel integral over
    the box the predicates intersect to, summed over the sample points in
    float64 and scaled by rows seen / rows kept; AVG is 0 where the count is
    at most 1e-3; its 95% interval is the normal-theory interval of the
    per-point terms (delta method for AVG);
  * an Eq answer on a column with a categorical sketch is the exact count of
    rows holding that code.

`Precision("bfloat16")` computes the same answers with every elementwise
result rounded to bfloat16 (sums kept in float32): the control that the
comparison must refuse.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
from scipy.special import erf

SQRT2 = math.sqrt(2.0)
INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
AVG_MIN_COUNT = 1e-3
Z95 = 1.959963984540054          # standard normal quantile at 0.975
K4_0 = 3.0 * INV_SQRT_2PI
K6_0 = -15.0 * INV_SQRT_2PI
R_K = 1.0 / (2.0 * math.sqrt(math.pi))


# --- reservoirs ---------------------------------------------------------------

def col_seed(store_seed: int, name: str) -> int:
    return int(store_seed) + zlib.crc32(name.encode()) % 1000


class Reservoir:
    """Algorithm R over rows (width None: scalars) with a seeded generator.
    `writes` holds the last `add`'s buffer writes (slots, rows) in the order
    they are made."""

    def __init__(self, capacity: int, seed: int, width: Optional[int] = None):
        self.capacity = int(capacity)
        self.rng = np.random.default_rng(seed)
        shape = (self.capacity,) if width is None else (self.capacity, width)
        self.buf = np.empty(shape, np.float32)
        self.n_seen = 0
        self.n_filled = 0
        self.version = 0
        self.writes = (np.empty(0, np.int64), self.buf[:0].copy())

    def add(self, values: np.ndarray) -> None:
        values = np.asarray(values, np.float32)
        if values.ndim == 1 and self.buf.ndim == 1:
            values = values.ravel()
        if values.shape[0] == 0:
            return
        self.version += 1
        k = 0
        slots, rows = [np.arange(self.n_filled, self.n_filled)], [values[:0]]
        if self.n_filled < self.capacity and self.n_seen == self.n_filled:
            k = min(self.capacity - self.n_filled, values.shape[0])
            self.buf[self.n_filled:self.n_filled + k] = values[:k]
            slots.append(np.arange(self.n_filled, self.n_filled + k))
            rows.append(values[:k])
            self.n_filled += k
            self.n_seen += k
        rest = values[k:]
        if rest.shape[0]:
            slot = self.rng.integers(0, self.n_seen + np.arange(rest.shape[0])
                                     + 1)
            keep = slot < self.n_filled
            self.buf[slot[keep]] = rest[keep]
            slots.append(slot[keep])
            rows.append(rest[keep])
            self.n_seen += rest.shape[0]
        self.writes = (np.concatenate(slots), np.concatenate(rows))

    def sample(self) -> np.ndarray:
        return self.buf[:self.n_filled].copy()


def key_name(key) -> str:
    return "|".join(key) if isinstance(key, tuple) else key


def build_reservoirs(store_cfg: dict, keys: Sequence,
                     first_batch: Dict[str, np.ndarray]) -> Dict[object, Reservoir]:
    """Reservoirs of `keys` (column names and column tuples) after the first
    ingest, as the configuration's store holds them."""
    cap, seed = int(store_cfg["capacity"]), int(store_cfg["seed"])
    tiered = {tuple(t) if len(t) > 1 else t[0] for t in store_cfg["tiered"]}
    top = int(store_cfg.get("n_tiers", 1)) - 1
    after = [tuple(j) for j in store_cfg["joints_after_ingest"]]
    out: Dict[object, Reservoir] = {}
    singles = {k for k in keys if not isinstance(k, tuple)}
    for k in after:
        if k in keys:
            singles |= set(k)
    for c in sorted(singles):
        s = col_seed(seed, c) + (top if c in tiered else 0)
        out[c] = Reservoir(cap, s)
        out[c].add(first_batch[c])
    for k in keys:
        if not isinstance(k, tuple):
            continue
        res = Reservoir(cap, col_seed(seed, key_name(k))
                        + (top if k in tiered else 0), width=len(k))
        if k in after:
            samples = [out[c].sample() for c in k]
            m = min(s.shape[0] for s in samples)
            res.add(np.stack([s[:m] for s in samples], axis=1))
            res.n_seen = min(out[c].n_seen for c in k)
        else:
            res.add(np.stack([first_batch[c] for c in k], axis=1))
        out[k] = res
    return {k: out[k] for k in keys}


def add_rows(res: Dict[object, Reservoir], batch: Dict[str, np.ndarray]) -> None:
    for k, r in res.items():
        if isinstance(k, tuple):
            r.add(np.stack([batch[c] for c in k], axis=1))
        else:
            r.add(batch[k])


# --- precision ----------------------------------------------------------------

class Precision:
    """float64 (the reference) or bfloat16 (the control): `r` rounds an
    elementwise result, `acc` is the dtype sums are kept in."""

    def __init__(self, name: str):
        self.name = name
        if name == "float64":
            self.r = lambda a: np.asarray(a, np.float64)
            self.acc = np.float64
        elif name == "bfloat16":
            import ml_dtypes
            bf = ml_dtypes.bfloat16
            self.r = lambda a: np.asarray(np.asarray(a, np.float32).astype(bf),
                                          np.float32)
            self.acc = np.float32
        else:
            raise ValueError(f"unknown precision {name!r}")


F64 = Precision("float64")


# --- PLUGIN bandwidth -------------------------------------------------------------

_PAIR_FNS = {}


def _pair_sums_fn(order: int, dtype_name: str, block: int):
    """Jitted sum over row blocks of K^(order)((x_i - x_j) / g), all i, j."""
    key = (order, dtype_name, block)
    if key in _PAIR_FNS:
        return _PAIR_FNS[key]
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    def kern(t):
        t2 = t * t
        if order == 4:
            poly = (t2 - 6.0) * t2 + 3.0
        else:
            poly = ((t2 - 15.0) * t2 + 45.0) * t2 - 15.0
        return poly * jnp.exp(-0.5 * t2)

    @jax.jit
    def fn(x, w, g):
        xs = x.astype(dt)
        ws = w.astype(jnp.float32)
        gi = (1.0 / g).astype(dt)

        def one(args):
            rows, wr = args
            t = (rows[:, None] - xs[None, :]) * gi
            k = kern(t).astype(jnp.float32)
            return jnp.sum(wr[:, None] * ws[None, :] * k)

        return jax.lax.map(one, (xs.reshape(-1, block),
                                 ws.reshape(-1, block)))

    _PAIR_FNS[key] = fn
    return fn


def _pair_sum(x: np.ndarray, g: float, order: int, prec: Precision,
              block: int = 1024) -> float:
    """sum_{i,j} K^(order)((x_i - x_j) / g) / sqrt(2 pi), float64 total."""
    n = x.shape[0]
    block = min(block, 1 << max(0, (n - 1).bit_length()))
    pad = (-n) % block
    xp = np.concatenate([x, np.zeros(pad, np.float32)]).astype(np.float32)
    w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    dt = "bfloat16" if prec.name == "bfloat16" else "float32"
    parts = _pair_sums_fn(order, dt, block)(xp, w, np.float32(g))
    return float(np.sum(np.asarray(parts, np.float64))) * INV_SQRT_2PI


def plugin_h(x: np.ndarray, prec: Precision = F64) -> float:
    """PLUGIN bandwidth of a 1-D sample (paper section 4.4, eqs. 12-19)."""
    x = prec.r(np.asarray(x, np.float32)).astype(np.float32)
    n = x.shape[0]
    xd = x.astype(np.float64)
    var = float(np.sum(xd * xd) / (n - 1.0)
                - np.sum(xd) ** 2 / (n * (n - 1.0)))
    sigma = math.sqrt(var)
    psi8 = 105.0 / (32.0 * math.sqrt(math.pi) * sigma ** 9)
    g1 = (-2.0 * K6_0 / (psi8 * n)) ** (1.0 / 9.0)
    psi6 = _pair_sum(x, g1, 6, prec) / (float(n) * n * g1 ** 7)
    g2 = (-2.0 * K4_0 / (psi6 * n)) ** (1.0 / 7.0)
    psi4 = _pair_sum(x, g2, 4, prec) / (float(n) * n * g2 ** 5)
    return (R_K / (psi4 * n)) ** 0.2


# --- answers ------------------------------------------------------------------

def box_of(spec: dict, cols: Tuple[str, ...], group: Optional[float]
           ) -> Tuple[np.ndarray, np.ndarray, int]:
    """(lo, hi, target axis) of a spec on `cols`: the intersection of its
    predicates per column; an unconstrained column is (-inf, inf)."""
    lo = {c: -math.inf for c in cols}
    hi = {c: math.inf for c in cols}

    def cut(c, a, b):
        lo[c] = max(lo[c], a)
        hi[c] = max(min(hi[c], b), lo[c])

    for p in spec["preds"]:
        if p[0] == "range":
            cut(p[1], p[2], p[3])
        elif p[0] == "eq":
            cut(p[1], p[2] - 0.5, p[2] + 0.5)
        else:
            for c, a, b in zip(p[1], p[2], p[3]):
                cut(c, a, b)
    if group is not None:
        cut(spec["group_by"], group - 0.5, group + 0.5)
    target = spec["target"]
    if target is None:
        target = next(p[1] if p[0] != "box" else p[1][0]
                      for p in spec["preds"])
    return (np.asarray([lo[c] for c in cols], np.float64),
            np.asarray([hi[c] for c in cols], np.float64), cols.index(target))


def kde_answers(boxes: Sequence[Tuple[np.ndarray, np.ndarray, int]],
                aggs: Sequence[str], x: np.ndarray, h: np.ndarray,
                n_seen: int, prec: Precision = F64, chunk: int = 16):
    """(estimate, CI half-width, reference count, target scale M) per box:
    the Gaussian product-kernel integrals over sample `x` (m, d)."""
    r = prec.r
    x = r(np.asarray(x, np.float32).reshape(x.shape[0], -1))
    m, d = x.shape
    h = r(np.asarray(h, np.float64).reshape(d))
    scale = n_seen / m
    out = []
    for s in range(0, len(boxes), chunk):
        part = boxes[s:s + chunk]
        q = len(part)
        lo = np.stack([b[0] for b in part])          # (q, d)
        hi = np.stack([b[1] for b in part])
        tgt = np.asarray([b[2] for b in part])
        za = r((r(lo)[:, None, :] - x[None]) / h)       # (q, m, d)
        zb = r((r(hi)[:, None, :] - x[None]) / h)
        d_phi_cdf = r(r(0.5 * (1.0 + erf(zb / SQRT2)))
                      - r(0.5 * (1.0 + erf(za / SQRT2))))
        d_pdf = r(r(INV_SQRT_2PI * np.exp(-0.5 * zb * zb))
                  - r(INV_SQRT_2PI * np.exp(-0.5 * za * za)))
        moment = r(r(x[None] * d_phi_cdf) - r(h * d_pdf))
        c = d_phi_cdf[..., 0]
        for j in range(1, d):
            c = r(c * d_phi_cdf[..., j])
        onehot = np.arange(d)[None, :] == tgt[:, None]      # (q, d)
        factors = np.where(onehot[:, None, :], moment, d_phi_cdf)
        sv = factors[..., 0]
        for j in range(1, d):
            sv = r(sv * factors[..., j])
        acc = prec.acc
        s1c = np.sum(c.astype(acc), axis=1, dtype=acc).astype(np.float64)
        s1s = np.sum(sv.astype(acc), axis=1, dtype=acc).astype(np.float64)
        s2c = np.sum((c * c).astype(acc), axis=1, dtype=acc).astype(np.float64)
        s2s = np.sum((sv * sv).astype(acc), axis=1,
                     dtype=acc).astype(np.float64)
        s12 = np.sum((c * sv).astype(acc), axis=1,
                     dtype=acc).astype(np.float64)
        corr = m / (m - 1.0)
        count = scale * s1c
        total = scale * s1s
        for i in range(q):
            agg = aggs[s + i]
            mt = float(np.max(np.abs(x[:, tgt[i]]))) + float(h[tgt[i]])
            if agg == "count":
                est = count[i]
                se = scale * math.sqrt(corr * max(s2c[i] - s1c[i] ** 2 / m,
                                                  0.0))
            elif agg == "sum":
                est = total[i]
                se = scale * math.sqrt(corr * max(s2s[i] - s1s[i] ** 2 / m,
                                                  0.0))
            else:
                ok = count[i] > AVG_MIN_COUNT
                ratio = s1s[i] / s1c[i] if ok else 0.0
                est = total[i] / count[i] if ok else 0.0
                quad = max(s2s[i] - 2.0 * ratio * s12[i]
                           + ratio * ratio * s2c[i], 0.0)
                se = (scale * math.sqrt(corr * quad)
                      / max(count[i], AVG_MIN_COUNT)) if ok else math.inf
            out.append((float(est), Z95 * float(se), float(count[i]), mt))
    return out


def exact_count(columns: Sequence[np.ndarray], value: float) -> int:
    """Rows holding `value` in a dictionary column (several batches)."""
    v = np.float32(value)
    return int(sum(int(np.count_nonzero(c == v)) for c in columns))


def exact_aggregate(spec: dict, data: Dict[str, np.ndarray],
                    group: Optional[float]) -> float:
    """The aggregate over every row (answer quality, not `correct`)."""
    n = len(next(iter(data.values())))
    mask = np.ones(n, bool)
    cols = []
    for p in spec["preds"]:
        if p[0] == "range":
            mask &= (data[p[1]] >= p[2]) & (data[p[1]] <= p[3])
            cols.append(p[1])
        elif p[0] == "eq":
            mask &= (data[p[1]] >= p[2] - 0.5) & (data[p[1]] <= p[2] + 0.5)
            cols.append(p[1])
        else:
            for c, a, b in zip(p[1], p[2], p[3]):
                mask &= (data[c] >= a) & (data[c] <= b)
                cols.append(c)
    if group is not None:
        g = data[spec["group_by"]]
        mask &= (g >= group - 0.5) & (g <= group + 0.5)
    count = float(np.count_nonzero(mask))
    if spec["agg"] == "count":
        return count
    total = float(np.sum(data[spec["target"] or cols[0]][mask],
                         dtype=np.float64))
    if spec["agg"] == "sum":
        return total
    return total / count if count else 0.0
