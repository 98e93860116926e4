"""Chip benchmark of the AQP serving path (see BENCHMARK.json at the root).

One run drives one cell, a deployment (`configs/<name>.json`) under one
traffic mix (`traffic/<name>.json`), through `TelemetryStore` ->
`QueryEngine` -> `AqpSession`, checks the answers against a plain reference
(`reference.py`) and prints one JSON result line:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: data generators in `datagen/`,
the reference's estimator for each bandwidth selector in `estimators/`,
layer patterns in `layers/`, operation counts in `cost/`, per-layer metric
readers in `metrics/`, correctness limits in `limits/`.  A new cell is new
files and new `BENCHMARK.json` entries; no existing file changes.  A new
configuration brings:

  * `configs/<name>.json` and `limits/<name>.json`, and a generator in
    `datagen/` if its data is new;
  * `estimators/<selector>.py` if its `engine.selector` has none (a
    selector without one is refused before any data is made);
  * for each program of its path whose work no entry counts yet: a cost
    module `cost/<name>.py` and an entry `layers/<layer>.costs/<name>.json`
    naming the program, the module and, for query programs, the answer
    path.
"""
