"""Reduction of a `jax.profiler` trace of the measured window to the numbers
the per-layer metrics read.

The TPU plane of an `.xplane.pb` (`/device:TPU:<n>`) carries one event per
executed XLA program on its "XLA Modules" line (named `jit_<fn>(<hash>)`)
and one per HLO operation on its "XLA Ops" line.  Host threads are on
`/host:CPU`, on the same clock; the benchmark's own calls appear there as
the `bench.*` annotations it wraps them in, and the window itself as
`bench.window`.

  * busy time is the union of program intervals inside the window, averaged
    over the TPU planes that ran anything;
  * each program is attributed to the first layer (`layers/<name>.json`,
    a list of regular expressions on the program name) that matches it, or
    to "other";
  * an idle gap (no program running) is attributed to the `bench.*` call
    that overlaps it most, or to "no benchmark call" (the program's own
    flusher thread, or nothing at all).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
NO_CALL = "no benchmark call"


def load_layers(names: Sequence[str]) -> List[Tuple[str, List[re.Pattern]]]:
    out = []
    for name in names:
        with open(os.path.join(HERE, "layers", f"{name}.json")) as fh:
            spec = json.load(fh)
        out.append((name, [re.compile(p) for p in spec["programs"]]))
    return out


def layer_names() -> List[str]:
    return sorted(os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(HERE, "layers"))
                  if f.endswith(".json"))


def _module_base(name: str) -> str:
    return name.split("(", 1)[0]


def _op_name(name: str) -> str:
    """'%fusion.6 = (f32[...]) fusion(...)' -> 'fusion'."""
    head = name.lstrip("%").split(" ", 1)[0]
    return re.sub(r"\.\d+$", "", head)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def reduce(path: str, layers: Optional[Sequence[str]] = None) -> dict:
    """Numbers of the traced window (`bench.window`) of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    layer_re = load_layers(layers if layers is not None else layer_names())

    host_spans: List[Tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    if ev.name == "bench.window":
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    else:
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    if window is None:
        raise RuntimeError("no bench.window annotation in the trace")
    w0, w1 = window

    def clip(a, b):
        return max(a, w0), min(b, w1)

    layer_ns: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    program_calls: Dict[str, int] = defaultdict(int)
    ops_ns: Dict[str, float] = defaultdict(float)
    busy_per_device = []
    busy_all: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = lines.get("XLA Modules")
        if mods is None:
            continue
        intervals = []
        for ev in mods.events:
            a, b = clip(ev.start_ns, ev.start_ns + ev.duration_ns)
            if b <= a:
                continue
            base = _module_base(ev.name)
            layer = next((name for name, pats in layer_re
                          if any(p.search(base) for p in pats)), "other")
            layer_ns[layer] += b - a
            calls[layer] += 1
            program_calls[base] += 1
            intervals.append((a, b))
        ops = lines.get("XLA Ops")
        if ops is not None and intervals:
            names = {}
            for ev in mods.events:
                names[(ev.start_ns, ev.start_ns + ev.duration_ns)] = \
                    _module_base(ev.name)
            starts = sorted(names)
            for ev in ops.events:
                a, b = clip(ev.start_ns, ev.start_ns + ev.duration_ns)
                if b <= a:
                    continue
                k = bisect.bisect_right(starts, (ev.start_ns, float("inf"))) - 1
                mod = names[starts[k]] if k >= 0 else "?"
                ops_ns[f"{mod}/{_op_name(ev.name)}"] += b - a
        if intervals:
            u = _union(intervals)
            busy_per_device.append(sum(b - a for a, b in u))
            busy_all.extend(u)

    window_ns = w1 - w0
    busy_ns = (sum(busy_per_device) / len(busy_per_device)
               if busy_per_device else 0.0)

    # idle gaps of the (first) busiest device, attributed to host calls
    gaps = []
    union = _union(busy_all)
    t = w0
    for a, b in union + [(w1, w1)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    host_spans.sort()
    idle_by: Dict[str, float] = defaultdict(float)
    hs_starts = [s[0] for s in host_spans]
    for a, b in gaps:
        best, best_ov = NO_CALL, 0.0
        k = bisect.bisect_right(hs_starts, b)
        for s0, s1, name in host_spans[max(0, k - 64):k]:
            ov = min(b, s1) - max(a, s0)
            if ov > best_ov:
                best, best_ov = name, ov
        idle_by[best] += b - a

    top_ops = sorted(ops_ns.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "devices": len(busy_per_device),
        "layer_s": {k: v * 1e-9 for k, v in layer_ns.items()},
        "layer_calls": dict(calls),
        "program_calls": dict(program_calls),
        "device_ops": [[k, v * 1e-9] for k, v in top_ops],
        "idle_gaps": [[k, v * 1e-9] for k, v in top_idle],
        "gaps": len(gaps),
    }
