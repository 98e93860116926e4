"""Every cell of BENCHMARK.json finds its files by name, and the query path
of `tpch.quiet` (no inserts) runs whole on the CPU at a small size."""
import os

import pytest

from bench import harness, traffic as traffic_mod
from bench.tests._cpu_run import small_run

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files(cell):
    cfg_entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(harness.ROOT, cfg_entry["file"])
    assert cfg["name"] == cfg_entry["name"]
    assert os.path.isfile(os.path.join(harness.HERE, "limits",
                                       f"{cfg['name']}.json"))
    assert os.path.isfile(os.path.join(harness.HERE, "estimators",
                                       f"{cfg['engine']['selector']}.py"))
    assert os.path.isfile(os.path.join(harness.HERE, "datagen",
                                       f"{cfg['data']['generator']}.py"))
    traffic = traffic_mod.load(cell["traffic"])
    assert traffic["loop"] == "open" and traffic["rate_qps"] > 0


def test_tpch_quiet_small_run_is_correct():
    result, extra = small_run("tpch.quiet")
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert "fresh_answer_ms" not in result["metrics"]
    assert result["metrics"]["query_p95_ms"]["value"] > 0
