"""Chip benchmark of the AQP serving path (see BENCHMARK.json at the root).

One run drives one cell, a deployment (`configs/<name>.json`) under one
traffic mix (`traffic/<name>.json`), through `TelemetryStore` ->
`QueryEngine` -> `AqpSession`, checks the answers against a plain reference
(`reference.py`) and prints one JSON result line:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: data generators in `datagen/`,
layer patterns in `layers/`, operation counts in `cost/`, per-layer metric
readers in `metrics/`, correctness limits in `limits/`.  A new cell is new
files and new `BENCHMARK.json` entries; no existing file changes.
"""
