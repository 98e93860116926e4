"""estimate_roofline: see BENCHMARK.json and PERF.md section 3."""
from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "estimate")
