"""The general traffic generator: turns a traffic file into query specs and
an arrival schedule, from the run's seed alone.

A traffic file (`traffic/<name>.json`) is data.  It names a loop kind
("open": Poisson arrivals at `rate_qps`, the only kind the harness drives),
the admission session's settings, and a `block` of
query templates with integer counts.  A run repeats the block a whole number
of times and shuffles it, so every seed sends the same number of queries of
every template, in another order.  `include` names another traffic file whose
keys this one overrides.

A query spec is a plain dict, independent of the program's types, so the
reference can read it:

    {"agg": "count" | "sum" | "avg",
     "preds": [["range", col, lo, hi] | ["eq", col, value]
               | ["box", [cols], [lo], [hi]]],
     "target": col | None, "group_by": col | None}

Predicate templates (bounds are floats; "min"/"max" are the column's extremes
in the generated data):

    {"column": c, "uniform": [lo, hi]}   a ~ U(lo, hi), b ~ U(a, hi), as
                                         serve.make_mixed_aqp_queries draws
    {"box": [c1, c2], "uniform": [lo, hi]}   the same per column, one Box term
    {"column": c, "lattice": [first, last, step]}   i <= j lattice indices,
                                         bounds half a step outside them
    {"column": c, "pairs": [[lo, hi], ...]}          one pair, uniformly
    {"column": c, "lo": V, "hi": V}      V: a number, {"choice": [...]},
                                         {"int": [a, b], "step": s,
                                         "offset": o} (o + s k, k in a..b), or
                                         for "hi" {"plus_lo": d}
    {"eq": c, "values": [...]}           column == one value (a code)
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """The traffic file `traffic/<name>.json`, with its `include` resolved."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        spec = json.load(fh)
    base = spec.pop("include", None)
    if base is None:
        return spec
    merged = load(base)
    merged.update(spec)
    return merged


def _value(v, rng: np.random.Generator, lo: Optional[float] = None) -> float:
    if isinstance(v, (int, float)):
        return float(v)
    if "choice" in v:
        return float(v["choice"][int(rng.integers(len(v["choice"])))])
    if "int" in v:
        a, b = v["int"]
        k = int(rng.integers(int(a), int(b) + 1))
        return float(v.get("offset", 0.0)) + float(v.get("step", 1.0)) * k
    if "plus_lo" in v:
        if lo is None:
            raise ValueError("plus_lo is only valid for an upper bound")
        return lo + float(v["plus_lo"])
    raise ValueError(f"unknown bound spec {v!r}")


def _extreme(v, col: str, stats: Dict[str, Tuple[float, float]]) -> float:
    if v == "min":
        return stats[col][0]
    if v == "max":
        return stats[col][1]
    return float(v)


def _uniform_pair(rng, lo: float, hi: float) -> Tuple[float, float]:
    a = float(rng.uniform(lo, hi))
    return a, float(rng.uniform(a, hi))


def make_pred(t: dict, rng: np.random.Generator,
              stats: Dict[str, Tuple[float, float]]) -> list:
    if "eq" in t:
        vals = t["values"]
        return ["eq", t["eq"], float(vals[int(rng.integers(len(vals)))])]
    if "box" in t:
        cols = list(t["box"])
        lo, hi = [], []
        for c in cols:
            a, b = _uniform_pair(rng, _extreme(t["uniform"][0], c, stats),
                                 _extreme(t["uniform"][1], c, stats))
            lo.append(a)
            hi.append(b)
        return ["box", cols, lo, hi]
    col = t["column"]
    if "uniform" in t:
        a, b = _uniform_pair(rng, _extreme(t["uniform"][0], col, stats),
                             _extreme(t["uniform"][1], col, stats))
    elif "lattice" in t:
        first, last, step = (float(v) for v in t["lattice"])
        n = int(round((last - first) / step)) + 1
        i = int(rng.integers(n))
        j = int(rng.integers(i, n))
        a, b = first + i * step - step / 2, first + j * step + step / 2
    elif "pairs" in t:
        a, b = (float(v) for v in t["pairs"][int(rng.integers(len(t["pairs"])))])
    else:
        a = _value(t["lo"], rng)
        b = _value(t["hi"], rng, lo=a)
    return ["range", col, a, b]


def make_spec(template: dict, rng: np.random.Generator,
              stats: Dict[str, Tuple[float, float]]) -> dict:
    aggs = template["aggregates"]
    agg = aggs[int(rng.integers(len(aggs)))]
    preds = [make_pred(t, rng, stats) for t in template["predicates"]]
    if agg == "count":
        preds += [make_pred(t, rng, stats)
                  for t in template.get("count_predicates", ())]
    target = template.get("target")
    if isinstance(target, dict):
        if "choice" in target:
            target = target["choice"][int(rng.integers(len(target["choice"])))]
        else:
            target = target.get(agg)
    if agg == "count":
        target = None
    return {"agg": agg, "preds": preds, "target": target,
            "group_by": template.get("group_by")}


def block_order(block: Sequence[dict], n: int,
                rng: np.random.Generator) -> List[int]:
    """Template index of each of `n` queries: whole blocks, shuffled."""
    size = sum(int(t["count"]) for t in block)
    if n % size:
        raise ValueError(f"{n} queries is not a whole number of blocks of "
                         f"{size}")
    one = [i for i, t in enumerate(block) for _ in range(int(t["count"]))]
    order = np.asarray(one * (n // size))
    rng.shuffle(order)
    return order.tolist()


def block_size(traffic: dict) -> int:
    return sum(int(t["count"]) for t in traffic["block"])


def open_schedule(traffic: dict, seconds: float, rng: np.random.Generator,
                  stats) -> Tuple[np.ndarray, List[dict]]:
    """(arrival offsets in seconds, specs) of an open loop: a whole number of
    blocks at `rate_qps` over the window, arrivals uniform and sorted (a
    Poisson process given its count)."""
    size = block_size(traffic)
    n = size * max(1, int(round(float(traffic["rate_qps"]) * seconds / size)))
    order = block_order(traffic["block"], n, rng)
    specs = [make_spec(traffic["block"][i], rng, stats) for i in order]
    offsets = np.sort(rng.uniform(0.0, seconds, n))
    return offsets, specs


def to_query(spec: dict):
    """The program's `AqpQuery` for a spec."""
    from repro.core import AqpQuery, Box, Eq, Range

    terms = []
    for p in spec["preds"]:
        if p[0] == "range":
            terms.append(Range(p[1], p[2], p[3]))
        elif p[0] == "eq":
            terms.append(Eq(p[1], p[2]))
        else:
            terms.append(Box(tuple(p[1]), tuple(p[2]), tuple(p[3])))
    return AqpQuery(spec["agg"], tuple(terms), target=spec["target"],
                    group_by=spec["group_by"])
