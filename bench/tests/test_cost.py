"""Each cost function against a hand count at a small shape, and the cost
entries a layer counts, from its file and from files beside it."""
import json
import os
import shutil

import pytest

from bench import harness


def test_range1d_hand_count():
    cost = harness.load_module("cost", "range1d")
    # 23 estimate + 29 moments operations per point, 10 points
    assert cost.query_flops(10) == 520.0
    assert cost.call_bytes(10) == 80.0


@pytest.mark.parametrize("d,per_point", [(2, 2 * 21 + 2 + 1 + 2
                                          + 2 * 21 + 2 + 1 + 3 + 5),
                                         (3, 3 * 21 + 4 + 1 + 2
                                          + 3 * 21 + 4 + 1 + 3 + 5)])
def test_box_hand_count(d, per_point):
    cost = harness.load_module("cost", "box")
    assert cost.query_flops(4, d) == 4 * per_point
    assert cost.call_bytes(4, d) == 2 * 4 * 4 * d


def test_grouped_hand_count():
    cost = harness.load_module("cost", "grouped")
    # d=3, G=3: shared (2 x 21 + 2) / 3 per part, 15 per category,
    # moments 3 x 21 + 4 + 1 + 3 + 5 = 76
    assert cost.query_flops(6, 3, 3) == pytest.approx(6 * (44 / 3 + 15 + 76))


def test_plugin_hand_count():
    cost = harness.load_module("cost", "plugin")
    # n = 4: 6 pairs x (13 + 11) + 3 x 4
    assert cost.call_flops(4) == 6 * 24 + 12
    assert cost.call_bytes(4) == 48


def _fixture_ctx():
    """The recorded chip trace (`data/window.xplane.pb`) with answers of
    every counted path: 24 ranges, 16 boxes and 6 GROUP BY parts in two
    grouped calls."""
    from bench import trace

    summary = trace.reduce(os.path.join(os.path.dirname(__file__), "data",
                                        "window.xplane.pb"))
    summary["program_calls"]["jit__aqp_grouped_sums"] = 2
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    parts = ([("range1d", 1, 1)] * 24 + [("box", 2, 1)] * 16
             + [("box:grouped", 3, 3)] * 6)
    return harness.LayerContext(summary, parts, 46, 1,
                                {"flushes": 5, "rows": 40}, 32768, peaks)


def test_every_layer_cost_exists():
    ctx = _fixture_ctx()
    for name in ("estimate", "fit"):
        for ent in ctx.costs(name):
            mod = ctx.cost(ent["cost"])
            assert hasattr(mod, "query_flops" if "path" in ent
                           else "call_flops")


def test_counts_of_the_layer_files():
    """Operations and bytes, and the roofline shares, on the fixture: the
    numbers the layer files' own entries gave before entries could also be
    found by file."""
    from bench.metrics import work

    ctx = _fixture_ctx()
    assert work(ctx, "estimate") == (114098176.0, 3407872.0)
    assert work(ctx, "fit") == (12884606976.0, 393216.0)
    assert harness.load_module("metrics", "estimate_roofline").read(ctx) \
        == 1.3681026724147605
    assert harness.load_module("metrics", "fit_roofline").read(ctx) \
        == 0.8698166374446055


FAKE_COST = """
def call_flops(n):
    return 1000.0 * n


def query_flops(n, d, groups=1):
    return 7.0 * n * d


def call_bytes(n, d=1):
    return 3.0 * n * d
"""


@pytest.mark.parametrize("layer,entry,flops,nbytes", [
    ("fit", {"program": "jit_lscv_h", "cost": "fake"},
     2 * 1000.0 * 32768, 2 * 3.0 * 32768),
    ("estimate", {"program": "jit__qmc_box_reduce", "path": "qmc",
                  "cost": "fake"}, 5 * 7.0 * 32768 * 2, 3 * 3.0 * 32768 * 2),
])
def test_cost_entry_added_as_a_file_is_counted(tmp_path, layer, entry,
                                               flops, nbytes):
    from bench.metrics import work

    for kind in ("layers", "cost"):
        shutil.copytree(os.path.join(harness.HERE, kind), tmp_path / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "cost" / "fake.py").write_text(FAKE_COST)
    (tmp_path / "layers" / f"{layer}.costs").mkdir()
    (tmp_path / "layers" / f"{layer}.costs" / "fake.json").write_text(
        json.dumps(entry))
    ctx = _fixture_ctx()
    before = work(ctx, layer)
    ctx.trace["program_calls"][entry["program"]] = 2 if layer == "fit" else 3
    ctx.answered = ctx.answered + [("qmc", 2, 1)] * 5
    ctx.root = str(tmp_path)
    assert ctx.costs(layer)[-1] == entry
    after = work(ctx, layer)
    assert after[0] - before[0] == pytest.approx(flops, rel=1e-12)
    assert after[1] - before[1] == pytest.approx(nbytes, rel=1e-12)
