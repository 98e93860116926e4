"""Plain reference for the AQP cells: the same semantics, written apart from
the program (it imports nothing of `repro` and takes nothing it made).

What a deployment's answers mean, as the configuration files state it:

  * a column's (or column tuple's) reservoir is an algorithm-R uniform
    sample of every row ingested, drawn with numpy's default generator from
    the store's seed (`seed + crc32(name) % 1000`; the top tier of a tiered
    ladder adds `n_tiers - 1`; a joint registered after ingest starts from
    the per-column samples zipped row by row).  `Reservoir` replays it from
    the benchmark's own data;
  * the bandwidth and the answers a KDE synopsis gives follow the
    configuration's `engine.selector`: `estimators/<selector>.py` computes
    both (`estimators/plugin.py`: PLUGIN per axis and the Gaussian product
    kernel);
  * an Eq answer on a column with a categorical sketch is the exact count of
    rows holding that code.

`Precision("bfloat16")` makes an estimator compute the same answers with
every elementwise result rounded to bfloat16 (sums kept in float32): the
control that the comparison must refuse.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

AVG_MIN_COUNT = 1e-3
Z95 = 1.959963984540054          # standard normal quantile at 0.975


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
