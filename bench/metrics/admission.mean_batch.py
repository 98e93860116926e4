"""admission.mean_batch: see BENCHMARK.json and PERF.md section 3."""
from bench.metrics import mean_batch


def read(ctx):
    return mean_batch(ctx)
