"""Each cost function against a hand count at a small shape."""
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


def test_every_layer_cost_exists():
    for name in ("estimate", "fit"):
        for ent in harness.load_json(harness.HERE, "layers",
                                     f"{name}.json")["costs"]:
            mod = harness.load_module("cost", ent["cost"])
            assert hasattr(mod, "query_flops" if "path" in ent
                           else "call_flops")
