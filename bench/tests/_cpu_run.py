"""A whole run of a cell on the CPU at a small size, for the tests: the
harness's look for a chip is skipped, everything after it runs."""
import time

from bench import harness


def small_run(cell, seconds=2.0, control=None, seed=20241016, config=None):
    overrides = {
        "config": {"data": {"rows": 20000}, "store": {"capacity": 512},
                   **(config or {})},
        "traffic": {"rate_qps": 24.0},
        "warm_sizes": (8,),
    }
    if cell.startswith("tpch"):
        overrides["traffic"]["refresh"] = None
        if cell == "tpch.refresh-quiesced":
            from bench import traffic as traffic_mod
            ref = dict(traffic_mod.load("refresh-quiesced")["refresh"], every_s=0.5,
                       rows=300)
            overrides["traffic"]["refresh"] = ref
    result, extra = harness.run_cell(cell, seed, seconds, False, time.time(),
                                     overrides=overrides, control=control,
                                     log=lambda msg: None)
    return result, extra
