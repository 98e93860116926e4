"""device.idle_share: see BENCHMARK.json and PERF.md section 3."""
from bench.metrics import idle_share


def read(ctx):
    return idle_share(ctx)
