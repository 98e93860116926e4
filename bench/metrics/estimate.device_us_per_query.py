"""estimate.device_us_per_query: see BENCHMARK.json and PERF.md section 3."""
from bench.metrics import per_query_us


def read(ctx):
    return per_query_us(ctx, "estimate")
