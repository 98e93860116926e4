"""Per-layer metric readers, one file per metric named as in BENCHMARK.json
(`metrics/<name>.py`), each defining `read(ctx) -> float | None`.  A reader
that finds nothing to read returns None and the metric is left out of the
result line; a share of a roofline or a peak is never reported as 0 for
want of data.  `ctx` is `bench.harness.LayerContext`."""
from __future__ import annotations

from typing import Optional, Tuple


def layer_seconds(ctx, layer: str) -> Optional[float]:
    if ctx.trace is None:
        return None
    sec = ctx.trace["layer_s"].get(layer, 0.0)
    return sec if sec > 0 else None


def work(ctx, layer: str) -> Tuple[float, float]:
    """(operations, bytes) the layer's algorithms needed in the window,
    counted from shapes by the cost modules of the layer's cost entries."""
    flops = nbytes = 0.0
    calls = ctx.trace["program_calls"]
    for ent in ctx.costs(layer):
        cost = ctx.cost(ent["cost"])
        n_calls = calls.get(ent["program"], 0)
        if "path" in ent:
            parts = [p for p in ctx.answered if p[0] == ent["path"]]
            flops += sum(cost.query_flops(ctx.n, d, g) for _p, d, g in parts)
            if parts:
                d_mean = sum(d for _p, d, _g in parts) / len(parts)
                nbytes += n_calls * cost.call_bytes(ctx.n, d_mean)
        else:
            flops += n_calls * cost.call_flops(ctx.n)
            nbytes += n_calls * cost.call_bytes(ctx.n)
    return flops, nbytes


def roofline(ctx, layer: str) -> Optional[float]:
    """Least time the chip's peaks allow for the layer's work, over the
    layer's device time, in percent."""
    sec = layer_seconds(ctx, layer)
    if sec is None:
        return None
    flops, nbytes = work(ctx, layer)
    if flops <= 0:
        return None
    least = max(flops / ctx.peak["flops_per_s"],
                nbytes / ctx.peak["bytes_per_s"])
    return 100.0 * least / sec


def per_query_us(ctx, layer: str) -> Optional[float]:
    sec = layer_seconds(ctx, layer)
    if sec is None or ctx.queries_done <= 0:
        return None
    return sec * 1e6 / ctx.queries_done


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def mean_batch(ctx) -> Optional[float]:
    flushes = ctx.admission.get("flushes", 0)
    if flushes <= 0:
        return None
    return ctx.admission["rows"] / flushes
