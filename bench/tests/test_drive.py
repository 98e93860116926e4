"""The stall watcher names the thread that holds a stall; the comparison
tells an answer computed on another sample than its version's."""
import sys
import time
import types

import numpy as np
import pytest

from bench import compare, drive, harness
from bench import reference as ref


def _hold_the_interpreter(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


@pytest.mark.parametrize("stall_s", [0.0, 1.5])
def test_stallwatch_sees_the_thread_that_holds_a_stall(stall_s):
    old = sys.getswitchinterval()
    watch = drive.StallWatch(time.perf_counter())
    time.sleep(0.3)
    sys.setswitchinterval(10.0)     # the busy thread keeps the lock
    try:
        _hold_the_interpreter(stall_s)
    finally:
        sys.setswitchinterval(old)
    time.sleep(0.3)
    late, snaps = watch.stop()
    if stall_s == 0.0:
        assert all(w[1] < 1.0 for w in late)
        return
    assert max(w[1] for w in late) > 1.0
    assert snaps and snaps[0]["since_beat_s"] >= 0.5
    lines = drive.stall_threads(snaps[0], watch.watchdog.names)
    main = [line for line in lines if line.startswith("MainThread (")]
    assert main and ": R " in main[0], lines


@pytest.mark.parametrize("read", ["previous_version", "torn_buffer"])
def test_worst_answer_shows_the_sample_it_was_computed_on(read):
    cfg = harness.load_json(harness.ROOT, "bench", "configs",
                            "tpch-lineitem-sf1.json")
    cfg["store"]["capacity"] = 256
    gen = harness.load_module("datagen", "tpch_lineitem")
    rng = np.random.default_rng(5)
    data = gen.generate(rng, 6000)
    batches = [gen.generate(rng, 500) for _ in range(2)]
    res = ref.build_reservoirs(cfg["store"], ["l_quantity"], data)
    ref.add_rows(res, batches[0])
    x, n_seen = res["l_quantity"].sample(), res["l_quantity"].n_seen
    if read == "previous_version":      # version 2's rows, labelled 3
        label = 3
    else:                               # version 2 with 3 of insert 3's
        label = 2                       # writes already in, labelled 2
        ref.add_rows(res, batches[1])
        slots, rows = res["l_quantity"].writes
        x[slots[:3]] = rows[:3]
    spec = {"agg": "sum", "preds": [["range", "l_quantity", 0.5, 24.5]],
            "target": "l_quantity", "group_by": None}
    plugin = harness.load_module("estimators", "plugin")
    (est, half, _c, _m), = plugin.answers(
        [ref.box_of(spec, ("l_quantity",), None)], ["sum"], x,
        plugin.bandwidth(x, ref.F64), n_seen, ref.F64)
    kde = types.SimpleNamespace(estimate=est, ci_lo=est - half,
                                ci_hi=est + half, group=None)
    eq = {"agg": "count", "preds": [["eq", "l_returnflag", 0.0]],
          "target": None, "group_by": None}
    count_v2 = ref.exact_count([data["l_returnflag"],
                                batches[0]["l_returnflag"]], 0.0)
    exact = types.SimpleNamespace(estimate=float(count_v2), group=None)
    work = {"store": cfg["store"], "data": data, "batches": batches,
            "kde": [(spec, kde, "l_quantity", label)],
            "exact": [(eq, exact, "l_returnflag", 3)],
            "h_prog": {}, "unanswered": 0, "stale": 0}
    out = compare.readings(work, plugin)
    worst = out["worst"]["estimate"]
    assert worst["version"] == label and worst["gap"] > 1e-5
    torn = worst["torn"]
    assert torn["gap_own_h"] < 1e-9 and torn["n_seen_of"] == "v2", torn
    if read == "previous_version":
        assert worst["gap_at"]["v2"] < 1e-9
        assert torn["offset"] == -torn["writes"][0]
    else:
        assert min(worst["gap_at"].values()) > 1e-5
        assert torn["offset"] == 3
    assert out["values"]["exact_gap"] > 0
    assert out["worst"]["exact"]["gap_at"] == {"v2": 0.0}
