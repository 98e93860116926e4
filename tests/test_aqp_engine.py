"""Unified declarative AQP API (core/aqp_query.py): AqpQuery normalization,
QueryEngine routing across execution paths, parity with the legacy stacks
(deprecation shims bit-for-bit), categorical Eq terms, GROUP BY, the batched
QMC fallback, and AqpResult metadata."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AqpQuery, Box, BoxQuery, BoxQueryBatch, Eq, GroupBy,
                        KDESynopsis, Query, QueryBatch, QueryEngine, Range)
from repro.core.aqp import batch_query_1d
from repro.core.aqp_multid import batch_query_box
from repro.core.aqp_query import from_box_query, from_query
from repro.data import TelemetryStore


def _store(rng, n=40_000, capacity=1024):
    a = rng.normal(0, 1, n).astype(np.float32)
    b = (0.8 * a + 0.6 * rng.normal(0, 1, n)).astype(np.float32)
    code = rng.integers(0, 4, n).astype(np.float32)
    store = TelemetryStore(capacity=capacity, seed=0)
    store.track_joint(("a", "b"))
    store.add_batch({"a": a, "b": b, "code": code})
    return store, a, b, code


# --- acceptance: one execute() call, every path, parity 1e-5 ----------------

def test_single_execute_answers_every_path(rng):
    """One QueryEngine.execute call answers a mixed batch of 1-D ranges,
    multi-d boxes, categorical equality, and full-H-fallback queries, and
    each answer agrees with the corresponding direct batched pass to 1e-5."""
    store, a, b, code = _store(rng)
    specs = [
        AqpQuery("count", (Range("a", -1.0, 1.0),)),
        AqpQuery("sum", (Range("b", -0.5, 2.0),), target="b"),
        AqpQuery("avg", (Box(("a", "b"), (-1.0, -1.0), (1.0, 1.0)),),
                 target="b"),
        AqpQuery("count", (Eq("code", 2.0),)),
        AqpQuery("count", (Range("a", -1.0, 1.0),), selector="lscv_H"),
    ]
    results = store.query(specs)
    assert [r.path for r in results] == ["range1d", "range1d", "box",
                                         "range1d", "qmc"]

    # direct closed-form passes against the same cached synopses
    syn_a = store.synopsis("a")
    got0 = float(batch_query_1d(
        syn_a.x, syn_a.h, jnp.asarray([-1.0], jnp.float32),
        jnp.asarray([1.0], jnp.float32), jnp.asarray([0], jnp.int32),
        jnp.float32(syn_a.n_source / syn_a.x.shape[0]))[0])
    assert results[0].estimate == pytest.approx(got0, rel=1e-5)

    syn_ab = store.joint_synopsis(("a", "b"))
    got2 = float(batch_query_box(
        syn_ab.x, syn_ab.h_diag(), jnp.asarray([[-1.0, -1.0]], jnp.float32),
        jnp.asarray([[1.0, 1.0]], jnp.float32), jnp.asarray([1], jnp.int32),
        jnp.asarray([2], jnp.int32),
        jnp.float32(syn_ab.n_source / syn_ab.x.shape[0]))[0])
    assert results[2].estimate == pytest.approx(got2, rel=1e-5)

    syn_code = store.synopsis("code")
    got3 = float(batch_query_1d(
        syn_code.x, syn_code.h, jnp.asarray([1.5], jnp.float32),
        jnp.asarray([2.5], jnp.float32), jnp.asarray([0], jnp.int32),
        jnp.float32(syn_code.n_source / syn_code.x.shape[0]))[0])
    assert results[3].estimate == pytest.approx(got3, rel=1e-5)

    # sanity vs exact answers (QMC and closed forms are both ~% accurate)
    exact = float(((a >= -1) & (a <= 1)).sum())
    assert results[0].estimate == pytest.approx(exact, rel=0.1)
    assert results[4].estimate == pytest.approx(exact, rel=0.15)
    assert results[3].estimate == pytest.approx(float((code == 2).sum()),
                                                rel=0.2)


def test_engine_matches_legacy_stacks_rtol(rng):
    """Mixed batch parity with the pre-refactor dispatch: compiled legacy
    Query/BoxQuery twins answer within 1e-5 relative error."""
    store, a, b, code = _store(rng)
    n_q = 64
    specs, legacy_r, legacy_b, order = [], [], [], []
    ops = ["count", "sum", "avg"]
    for i in range(n_q):
        op = ops[i % 3]
        if i % 3 == 2:
            lo = tuple(rng.uniform(-2.0, 0.0, 2))
            hi = tuple(np.asarray(lo) + rng.uniform(0.5, 3.0, 2))
            specs.append(AqpQuery(op, (Box(("a", "b"), lo, hi),), target="a"))
            legacy_b.append(BoxQuery(op, lo, hi, columns=("a", "b"),
                                     target="a"))
            order.append(("b", len(legacy_b) - 1))
        else:
            col = "a" if i % 2 else "b"
            lo = float(rng.uniform(-2.0, 1.0))
            hi = lo + float(rng.uniform(0.1, 2.0))
            specs.append(AqpQuery(op, (Range(col, lo, hi),),
                                  target=None if op == "count" else col))
            legacy_r.append(Query(op, lo, hi, column=col))
            order.append(("r", len(legacy_r) - 1))
    got = store.engine().answers(specs)
    want_r = store.query_batch(legacy_r)
    want_b = store.query_box_batch(legacy_b)
    want = np.asarray([{"r": want_r, "b": want_b}[k][i] for k, i in order])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --- deprecation shims ------------------------------------------------------

def test_querybatch_shim_bitwise_and_warns(rng):
    store, *_ = _store(rng, n=8000, capacity=512)
    qs = [Query("count", -1.0, 1.0, column="a"),
          Query("sum", -0.5, 2.0, column="b"),
          Query("avg", 0.0, 1.5, column="a")]
    synopses = {c: store.synopsis(c) for c in ("a", "b")}
    with pytest.warns(DeprecationWarning, match="QueryBatch.run"):
        legacy = QueryBatch(qs).run(synopses)
    engine = QueryEngine(store).answers([from_query(q) for q in qs])
    np.testing.assert_array_equal(legacy, engine)


def test_boxquerybatch_shim_bitwise_and_warns(rng):
    store, *_ = _store(rng, n=8000, capacity=512)
    qs = [BoxQuery("count", (-1, -1), (1, 1), columns=("a", "b")),
          BoxQuery("sum", (-2, -1), (0, 2), columns=("a", "b"), target="b"),
          BoxQuery("avg", (-1, 0), (1, 2), columns=("a", "b"), target="a")]
    synopses = {("a", "b"): store.joint_synopsis(("a", "b"))}
    with pytest.warns(DeprecationWarning, match="BoxQueryBatch.run"):
        legacy = BoxQueryBatch(qs).run(synopses)
    engine = QueryEngine(store).answers([from_box_query(q) for q in qs])
    np.testing.assert_array_equal(legacy, engine)


# --- categorical Eq and GROUP BY --------------------------------------------

def test_eq_counts_dictionary_codes(rng):
    n = 30_000
    code = rng.choice([0, 1, 2, 3], size=n,
                      p=[0.4, 0.3, 0.2, 0.1]).astype(np.float32)
    store = TelemetryStore(capacity=2048, seed=0)
    store.add_batch({"code": code})
    res = store.query([AqpQuery("count", (Eq("code", v),))
                       for v in (0.0, 1.0, 2.0, 3.0)],
                      selector="silverman")
    for v, r in zip((0, 1, 2, 3), res):
        assert r.estimate == pytest.approx(float((code == v).sum()), rel=0.2)
    # the code buckets partition the range: totals agree much tighter
    total = sum(r.estimate for r in res)
    assert total == pytest.approx(n, rel=0.05)


def test_group_by_discovers_codes_and_matches_eq(rng):
    store, a, b, code = _store(rng)
    store.track_joint(("code", "b"))          # backfilled joint for the demo
    store.add_batch({"a": a, "b": b, "code": code})   # stream real rows too
    grouped = store.engine().execute(
        AqpQuery("count", (Range("b", -1.0, 1.0),), group_by="code"))
    assert [r.group for r in grouped] == [0.0, 1.0, 2.0, 3.0]
    assert all(r.path == "box:grouped" for r in grouped)
    # each group row matches the equivalent explicit Eq conjunction; the
    # grouped kernel factors the shared-axis product out of the per-category
    # pass, so agreement is to float tolerance rather than bitwise
    explicit = store.engine().answers(
        [AqpQuery("count", (Range("b", -1.0, 1.0), Eq("code", v)))
         for v in (0.0, 1.0, 2.0, 3.0)])
    np.testing.assert_allclose([r.estimate for r in grouped], explicit,
                               rtol=1e-5, atol=1e-3)
    sel = (b >= -1) & (b <= 1)
    for r in grouped:
        # the joint stream is the backfill window plus one real pass over the
        # data, so the relation it represents is the data twice
        exact = 2.0 * float((sel & (code == r.group)).sum())
        assert r.estimate == pytest.approx(exact, rel=0.35, abs=400)

    pinned = store.engine().execute(
        AqpQuery("count", (Range("b", -1.0, 1.0),),
                 group_by=GroupBy("code", values=(2.0, 0.0))))
    assert [r.group for r in pinned] == [2.0, 0.0]


def test_group_by_with_implicit_target(rng):
    """SUM/AVG over one predicate column may leave the target implicit even
    under GROUP BY — the group term must not count as a predicate column."""
    n = 20_000
    code = rng.integers(0, 3, n).astype(np.float32)
    b = (code + rng.normal(0, 0.3, n)).astype(np.float32)
    store = TelemetryStore(capacity=1024, seed=0)
    store.track_joint(("code", "b"))
    store.add_batch({"code": code, "b": b})
    implicit = store.engine().execute(
        AqpQuery("avg", (Range("b", -2.0, 5.0),), group_by="code"))
    explicit = store.engine().execute(
        AqpQuery("avg", (Range("b", -2.0, 5.0),), target="b",
                 group_by="code"))
    np.testing.assert_array_equal([r.estimate for r in implicit],
                                  [r.estimate for r in explicit])
    for r in implicit:
        assert r.estimate == pytest.approx(float(r.group), abs=0.3)


def test_execute_specs_rejects_store_only_features(rng):
    from repro.core.aqp_query import execute_specs

    syn = KDESynopsis.fit(
        jnp.asarray(rng.normal(0, 1, 1000).astype(np.float32)),
        max_sample=256)
    with pytest.raises(ValueError, match="group_by needs a store"):
        execute_specs([AqpQuery("count", (Range(None, 0, 1),),
                                group_by="code")], syn)
    with pytest.raises(ValueError, match="selector override needs"):
        execute_specs([AqpQuery("count", (Range(None, 0, 1),),
                                selector="lscv_H")], syn)


def test_group_by_guards(rng):
    store, *_ = _store(rng, n=2000, capacity=256)
    with pytest.raises(KeyError, match="group_by column"):
        store.engine().execute(AqpQuery("count", (Range("a", 0, 1),),
                                        group_by="missing"))
    many = rng.normal(0, 100, 2000).astype(np.float32)
    store.add_batch({"many": many})
    with pytest.raises(ValueError, match="max_groups"):
        store.engine().execute(AqpQuery("count", (Range("a", 0, 1),),
                                        group_by="many"))


def test_group_by_single_category_stays_on_plain_path(rng):
    """A one-category GROUP BY has nothing to factor; it runs the ordinary
    box path (the grouped kernel needs >= 2 siblings)."""
    store, *_ = _store(rng)
    store.track_joint(("code", "b"))
    only = store.engine().execute(
        AqpQuery("count", (Range("b", -1.0, 1.0),),
                 group_by=GroupBy("code", values=(2.0,))))
    assert [r.path for r in only] == ["box"]


def test_grouped_kernel_with_group_column_target(rng):
    """SUM/AVG whose target IS the group column exercises the grouped
    kernel's moment-on-group-axis branch."""
    n = 20_000
    code = rng.integers(0, 3, n).astype(np.float32)
    b = (code + rng.normal(0, 0.3, n)).astype(np.float32)
    store = TelemetryStore(capacity=1024, seed=0)
    store.track_joint(("code", "b"))
    store.add_batch({"code": code, "b": b})
    grouped = store.engine().execute(
        AqpQuery("avg", (Range("b", -2.0, 5.0),), target="code",
                 group_by="code"))
    assert all(r.path == "box:grouped" for r in grouped)
    explicit = store.engine().answers(
        [AqpQuery("avg", (Range("b", -2.0, 5.0), Eq("code", v)),
                  target="code") for v in (0.0, 1.0, 2.0)])
    np.testing.assert_allclose([r.estimate for r in grouped], explicit,
                               rtol=1e-4, atol=1e-3)
    for r in grouped:
        # AVG(code) within a category's code window ~ the category code
        assert r.estimate == pytest.approx(float(r.group), abs=0.1)


# --- exact categorical sketches ----------------------------------------------

def test_exact_eq_path_count_sum_avg(rng):
    """Eq terms on a tracked dictionary column answer from the per-code
    frequency sketch: exact, path=="exact", rel_width==0.0 (no smoothing),
    zero-width confidence intervals."""
    n = 25_000
    code = rng.choice([0, 1, 2, 3], size=n,
                      p=[0.4, 0.3, 0.2, 0.1]).astype(np.float32)
    store = TelemetryStore(capacity=1024, seed=0)
    store.track_categorical("code")
    store.add_batch({"code": code})
    res = store.query([
        AqpQuery("count", (Eq("code", 2.0),)),
        AqpQuery("sum", (Eq("code", 2.0),)),
        AqpQuery("avg", (Eq("code", 2.0),)),
        AqpQuery("count", (Eq("code", 7.0),)),        # absent code
    ])
    n2 = float((code == 2).sum())
    assert [r.path for r in res] == ["exact"] * 4
    assert res[0].estimate == n2
    assert res[1].estimate == 2.0 * n2
    assert res[2].estimate == 2.0
    assert res[3].estimate == 0.0
    assert all(r.rel_width == 0.0 for r in res)
    assert all(r.ci_lo == r.estimate == r.ci_hi for r in res)
    assert all(r.n_effective == n for r in res)
    assert res[0].synopsis_version == store.columns["code"].version


def test_rel_width_ordering_exact_best(rng):
    """The deprecated accuracy proxy must rank exact answers BEST (0.0),
    constrained KDE answers in between (finite), and genuinely unconstrained
    estimates worst (inf) — regression for the old rel_width=inf-on-exact
    bug."""
    n = 20_000
    store = TelemetryStore(capacity=512, seed=0)
    store.track_categorical("code")
    store.add_batch({"code": rng.integers(0, 4, n).astype(np.float32),
                     "val": rng.normal(0.0, 1.0, n).astype(np.float32)})
    exact, ranged, uncon = store.query([
        AqpQuery("count", (Eq("code", 2.0),)),           # exact sketch
        AqpQuery("count", (Range("val", -1.0, 1.0),)),   # constrained KDE
        AqpQuery("sum", (), target="val"),               # whole-table SUM
    ])
    assert exact.path == "exact" and exact.rel_width == 0.0
    assert ranged.path == "range1d" and np.isfinite(ranged.rel_width) \
        and ranged.rel_width > 0.0
    assert uncon.rel_width == np.inf
    assert exact.rel_width < ranged.rel_width < uncon.rel_width


def test_exact_eq_falls_back_without_full_coverage(rng):
    """Untracked columns, sketches registered after data, and range (non-Eq)
    predicates all stay on the KDE path."""
    n = 10_000
    code = rng.integers(0, 4, n).astype(np.float32)
    store = TelemetryStore(capacity=1024, seed=0)
    store.add_batch({"code": code})
    # untracked: KDE code-window estimate
    r = store.query([AqpQuery("count", (Eq("code", 1.0),))])[0]
    assert r.path == "range1d"
    # tracked late: sketch misses the first batch -> still KDE
    store.track_categorical("code")
    store.add_batch({"code": code})
    r = store.query([AqpQuery("count", (Eq("code", 1.0),))])[0]
    assert r.path == "range1d"
    assert store.stats()["categoricals"]["code"]["exact"] is False


def test_exact_eq_mixes_with_kde_paths_in_one_batch(rng):
    """One batch mixing exact Eq, ranges, and boxes scatters back to
    submission order with per-row paths."""
    store, a, b, code = _store(rng)
    store2 = TelemetryStore(capacity=1024, seed=0)
    store2.track_categorical("code")
    store2.track_joint(("a", "b"))
    store2.add_batch({"a": a, "b": b, "code": code})
    res = store2.query([
        AqpQuery("count", (Eq("code", 0.0),)),
        AqpQuery("count", (Range("a", -1.0, 1.0),)),
        AqpQuery("count", (Box(("a", "b"), (-1, -1), (1, 1)),)),
        AqpQuery("count", (Eq("code", 3.0),)),
    ])
    assert [r.path for r in res] == ["exact", "range1d", "box", "exact"]
    assert res[0].estimate == float((code == 0).sum())
    assert res[3].estimate == float((code == 3).sum())
    # Eq + Range on the same dictionary column is a range conjunction, not a
    # pure code window: it must NOT take the exact path
    r = store2.query([AqpQuery("count", (Eq("code", 1.0),
                                         Range("code", 0.0, 2.0)))])[0]
    assert r.path == "range1d"


def test_exact_eq_group_by_same_column(rng):
    """COUNT .. GROUP BY code with no other predicate is a pure code window
    per category: every row exact."""
    n = 8_000
    code = rng.integers(0, 3, n).astype(np.float32)
    store = TelemetryStore(capacity=512, seed=0)
    store.track_categorical("code")
    store.add_batch({"code": code})
    rows = store.engine().execute(AqpQuery("count", (), group_by="code"))
    assert [r.path for r in rows] == ["exact"] * 3
    for r in rows:
        assert r.estimate == float((code == r.group).sum())
    assert sum(r.estimate for r in rows) == float(n)


# --- normalization / validation ---------------------------------------------

def test_aqp_query_validation():
    with pytest.raises(ValueError, match="unknown aggregate"):
        AqpQuery("median", (Range("a", 0, 1),))
    with pytest.raises(ValueError, match="no target"):
        AqpQuery("count", (Range("a", 0, 1),), target="a")
    with pytest.raises(ValueError, match="at least one predicate"):
        AqpQuery("count", ())
    with pytest.raises(ValueError, match="predicate term or a target"):
        AqpQuery("sum", ())
    with pytest.raises(TypeError, match="Range/Box/Eq"):
        AqpQuery("count", ("a",))
    with pytest.raises(ValueError, match="mismatch"):
        Box(("a", "b"), (0, 0), (1, 1, 1))
    with pytest.raises(ValueError, match="names"):
        Box(("a",), (0, 0), (1, 1))
    with pytest.raises(ValueError, match="halfwidth"):
        Eq("a", 1.0, halfwidth=0.0)
    # case-insensitive aggregate spelling is normalized
    assert AqpQuery("COUNT", (Range("a", 0, 1),)).aggregate == "count"


def test_engine_compile_errors(rng):
    store, *_ = _store(rng, n=2000, capacity=256)
    eng = store.engine()
    with pytest.raises(ValueError, match="mix named and positional"):
        eng.execute(AqpQuery("count", (Range("a", 0, 1), Range(None, 0, 1))))
    with pytest.raises(ValueError, match="explicit target"):
        eng.execute(AqpQuery("sum", (Range("a", 0, 1), Range("b", 0, 1))))
    with pytest.raises(ValueError, match="name a column"):
        eng.execute(AqpQuery("count", (Range(None, 0, 1),)))
    with pytest.raises(KeyError, match="track_joint"):
        eng.execute(AqpQuery("count", (Range("a", 0, 1), Range("code", 0, 1))))
    with pytest.raises(TypeError, match="AqpQuery"):
        eng.execute([Query("count", 0, 1, column="a")])


def test_mapping_miss_lists_mixed_keys(rng):
    """A unified mapping may mix plain column keys with column tuples; the
    missing-key diagnostic must not crash sorting them against each other."""
    from repro.core.aqp_query import execute_specs

    data = rng.normal(0, 1, (1000, 2)).astype(np.float32)
    syn1 = KDESynopsis.fit(jnp.asarray(data[:, 0]), max_sample=256)
    syn2 = KDESynopsis.fit(jnp.asarray(data), max_sample=256)
    mixed = {"a": syn1, ("a", "b"): syn2}
    with pytest.raises(KeyError, match="no synopsis for column 'c'"):
        execute_specs([AqpQuery("count", (Range("c", -1, 1),))], mixed)
    with pytest.raises(KeyError, match="no joint synopsis"):
        execute_specs([AqpQuery("count", (Range("a", -1, 1),
                                          Range("c", -1, 1)))], mixed)


def test_conjunction_intersects_repeated_columns(rng):
    """Two Range terms on the same column intersect; an empty intersection
    collapses to a zero-measure box (COUNT ~ 0, AVG exactly 0)."""
    store, a, *_ = _store(rng)
    eng = store.engine()
    both = eng.answers([
        AqpQuery("count", (Range("a", -1.0, 2.0), Range("a", 0.0, 5.0))),
        AqpQuery("count", (Range("a", 0.0, 2.0),)),
    ])
    assert both[0] == pytest.approx(both[1], rel=1e-6)
    empty = eng.execute([
        AqpQuery("count", (Range("a", -2.0, -1.0), Range("a", 1.0, 2.0))),
        AqpQuery("avg", (Range("a", -2.0, -1.0), Range("a", 1.0, 2.0)),
                 target="a"),
    ])
    assert empty[0].estimate == pytest.approx(0.0, abs=1e-3)
    assert empty[1].estimate == 0.0


def test_target_outside_predicates_uses_wide_axis(rng):
    """SUM/AVG of a column not mentioned in the predicates adds an
    unconstrained axis: AVG(b) WHERE code == v through the (code, b) joint."""
    n = 30_000
    code = rng.integers(0, 3, n).astype(np.float32)
    b = (code * 2.0 + rng.normal(0, 0.5, n)).astype(np.float32)
    store = TelemetryStore(capacity=2048, seed=0)
    store.track_joint(("code", "b"))
    store.add_batch({"code": code, "b": b})
    res = store.engine().execute(
        [AqpQuery("avg", (Eq("code", v),), target="b") for v in (0.0, 2.0)])
    for r, v in zip(res, (0.0, 2.0)):
        assert r.estimate == pytest.approx(float(b[code == v].mean()),
                                           abs=0.15)
        assert r.rel_width < np.inf           # the code axis is constrained
    whole = store.engine().execute(AqpQuery("sum", (), target="b"))[0]
    assert whole.rel_width == np.inf          # no constrained axis at all
    assert whole.estimate == pytest.approx(float(b.sum()), rel=0.1)


def test_set_matching_reorders_to_tracked_joint(rng):
    """Predicate column order need not match the tracked joint tuple."""
    store, a, b, _ = _store(rng)
    fwd = store.engine().answers(
        [AqpQuery("count", (Range("a", -1, 1), Range("b", -1, 1)))])
    rev = store.engine().answers(
        [AqpQuery("count", (Range("b", -1, 1), Range("a", -1, 1)))])
    np.testing.assert_array_equal(fwd, rev)
    sel = (np.abs(a) <= 1) & (np.abs(b) <= 1)
    assert fwd[0] == pytest.approx(float(sel.sum()), rel=0.1)


def test_result_metadata(rng):
    store, *_ = _store(rng)
    narrow, wide = store.engine().execute([
        AqpQuery("count", (Range("a", 0.0, 0.2),)),
        AqpQuery("count", (Range("a", -2.0, 2.0),)),
    ])
    assert narrow.rel_width < wide.rel_width
    assert narrow.synopsis_version == store.columns["a"].version
    assert float(narrow) == narrow.estimate
    assert narrow.query.aggregate == "count"
    store.add_batch({"a": np.ones(10, np.float32)})
    bumped = store.engine().execute(
        AqpQuery("count", (Range("a", 0.0, 0.2),)))[0]
    assert bumped.synopsis_version == narrow.synopsis_version + 1


@pytest.mark.parametrize("backend", ["pallas"])
def test_engine_pallas_backend_paths(rng, backend):
    store, *_ = _store(rng, n=8000, capacity=512)
    specs = [AqpQuery("count", (Range("a", -1, 1),)),
             AqpQuery("count", (Box(("a", "b"), (-1, -1), (1, 1)),))]
    res = store.engine(backend=backend).execute(specs)
    assert [r.path for r in res] == ["range1d:pallas", "box:pallas"]
    want = store.engine().answers(specs)
    got = np.asarray([r.estimate for r in res])
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-2)


@pytest.fixture
def fit_spans():
    """Ring tracing on for one test, with a fresh tracer: yields a function
    that lists the `synopsis.fit` spans' (selector, backend)."""
    from repro import obs
    from repro.obs import Tracer
    prev, was = obs.set_tracer(Tracer()), obs.enabled()
    obs.enable()
    yield lambda: [(sp.attrs["selector"], sp.attrs["backend"])
                   for sp in obs.get_tracer().spans()
                   if sp.name == "synopsis.fit"]
    if not was:
        obs.disable()
    obs.set_tracer(prev)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_plugin_refit_follows_engine_backend(rng, monkeypatch, fit_spans,
                                             backend):
    """A refit after add_batch runs the PLUGIN pair sums on the Pallas
    kernel exactly when the engine's backend is pallas."""
    from repro.core.plugin import plugin_bandwidth
    from repro.kernels import ops as kops
    kinds = []
    real = kops.pairwise_scaled_ksum

    def spy(x, g, kind="k4", tile=None):
        kinds.append(kind)
        return real(x, g, kind=kind, tile=tile)

    monkeypatch.setattr(kops, "pairwise_scaled_ksum", spy)
    store, a, b, code = _store(rng, n=4000, capacity=256)
    eng = store.engine(backend=backend)
    spec = AqpQuery("count", (Range("a", -1.0, 1.0),))
    eng.execute(spec)
    store.add_batch({"a": a[:500], "b": b[:500], "code": code[:500]})
    plugin_bandwidth.clear_cache()      # the spy is seen when the refit traces
    kinds.clear()
    res = eng.execute(spec)[0]
    assert fit_spans()[-1] == ("plugin", backend)
    assert kinds == (["k6", "k4"] if backend == "pallas" else [])
    want = store.synopsis("a").h    # the cached fit, whichever backend ran
    h = plugin_bandwidth(store.columns["a"].sample(), backend="jnp").h
    assert abs(float(want) / float(h) - 1) < 1e-4
    assert np.isfinite(res.estimate)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_lscv_fit_follows_engine_backend(rng, monkeypatch, fit_spans,
                                         backend):
    """An LSCV_h fit runs its grid sums on the Pallas kernel exactly when the
    engine's backend is pallas, and either backend picks the same grid
    point: the bandwidth is the jnp LSCV_h minimiser times the sample's
    standard deviation."""
    from repro.core.lscv import lscv_h
    from repro.kernels import ops as kops
    calls = []
    real = kops.lscv_grid_sums

    def spy(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(kops, "lscv_grid_sums", spy)
    lscv_h.clear_cache()                # the spy is seen when the fit traces
    store, *_ = _store(np.random.default_rng(3), n=4000, capacity=256)
    spec = AqpQuery("count", (Range("a", -1.0, 1.0),), selector="lscv_h")
    store.engine(backend=backend).execute(spec)
    assert fit_spans() == [("lscv_h", backend)]
    assert calls == ([(256, 1)] if backend == "pallas" else [])
    syn = store.synopsis("a", "lscv_h")
    sample = store.columns["a"].sample()
    want = lscv_h(jnp.asarray(sample))
    assert float(syn.grid_h) == float(want.h)
    assert float(syn.sigma) == pytest.approx(float(np.std(sample, ddof=1)),
                                             rel=1e-5)
    assert float(syn.h) == pytest.approx(float(want.h) * float(syn.sigma),
                                         rel=1e-6)


def _lscv_h64(x: np.ndarray) -> float:
    """Float64 LSCV_h bandwidth h sigma of a 1-D sample: g(h) (eq. 24 in
    the eq. 43 form) on eq. 29's 150-point grid, minimised."""
    x = np.asarray(x, np.float64)
    n = x.size
    sigma = np.std(x, ddof=1)
    h0 = (4.0 / 3.0) ** 0.2 * n ** -0.2            # eq. 28 at d = 1
    grid = np.linspace(h0 / 4.0, 4.0 * h0, 150)
    i, j = np.triu_indices(n, 1)
    s = ((x[i] - x[j]) / sigma) ** 2
    c_k = (2.0 * np.pi) ** -0.5 / sigma
    c_kk = (4.0 * np.pi) ** -0.5 / sigma
    g = [(2.0 / n ** 2 * np.sum(c_kk * np.exp(-s / (4 * h * h))
                                - 2.0 * c_k * np.exp(-s / (2 * h * h)))
          + c_kk / n) / h for h in grid]
    return float(grid[int(np.argmin(g))] * sigma)


def _box_count64(x: np.ndarray, h: np.ndarray, lo, hi, scale: float):
    """Float64 COUNT of the Gaussian product KDE of x (m, d) over a box."""
    from scipy.special import ndtr
    x = np.asarray(x, np.float64).reshape(x.shape[0], -1)
    mass = np.prod(ndtr((np.asarray(hi) - x) / h)
                   - ndtr((np.asarray(lo) - x) / h), axis=1)
    return scale * float(np.sum(mass))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_lscv_h_answers_integrate_per_axis_bandwidths(backend):
    """Served lscv_h range, box and GROUP BY answers equal the float64
    product-kernel integral at bandwidth h_j sigma_j per axis, h_j each
    axis's own LSCV_h grid minimiser (on columns whose sigma is far from
    1, where the bare grid value would be another KDE)."""
    rng = np.random.default_rng(11)
    n = 6000
    a = rng.normal(40.0, 8.0, n).astype(np.float32)
    b = (0.05 * a + rng.normal(0.0, 0.3, n)).astype(np.float32)
    code = rng.integers(0, 4, n).astype(np.float32)
    store = TelemetryStore(capacity=256, seed=0)
    store.track_joint(("a", "b"))
    store.track_joint(("code", "a"))
    store.add_batch({"a": a, "b": b, "code": code})
    eng = store.engine(selector="lscv_h", backend=backend)
    rng_q = AqpQuery("count", (Range("a", 30.0, 45.0),))
    box_q = AqpQuery("count", (Box(("a", "b"), (30.0, 1.5), (45.0, 2.5)),))
    grp_q = AqpQuery("count", (Range("a", 30.0, 45.0),), group_by="code")
    got_range, got_box = (r.estimate for r in eng.execute([rng_q, box_q]))
    got_groups = eng.execute(grp_q)

    def want(key, lo, hi):
        res = store.joints[key] if isinstance(key, tuple) \
            else store.columns[key]
        x = res.sample().reshape(res.sample().shape[0], -1)
        h = np.asarray([_lscv_h64(x[:, j]) for j in range(x.shape[1])])
        return _box_count64(x, h, lo, hi, res.n_seen / x.shape[0])

    assert got_range == pytest.approx(want("a", [30.0], [45.0]), rel=1e-4)
    assert got_box == pytest.approx(
        want(("a", "b"), [30.0, 1.5], [45.0, 2.5]), rel=1e-4)
    assert [r.group for r in got_groups] == [0.0, 1.0, 2.0, 3.0]
    for r in got_groups:
        assert r.estimate == pytest.approx(
            want(("code", "a"), [r.group - 0.5, 30.0], [r.group + 0.5, 45.0]),
            rel=1e-4)


def test_fit_counters_count_refits_an_insert_forces(rng):
    """Every fit of `_fit_cached` counts in the process-wide
    `aqp.synopsis.fits` (the session's `stats()["fits"]`); one that replaces
    a synopsis an insert left stale carries `stale=1` (a cache hit counts
    nowhere)."""
    from repro import obs
    store, a, b, code = _store(rng, n=4000, capacity=256)
    reg = obs.get_registry()
    spec = AqpQuery("count", (Range("a", -1.0, 1.0),))
    with store.session(watermark=1) as session:
        fits0 = session.stats()["fits"]
        refits0 = reg.sum_counter("aqp.synopsis.fits", stale=1)

        def counts():
            return (session.stats()["fits"] - fits0,
                    reg.sum_counter("aqp.synopsis.fits", stale=1) - refits0)

        session.submit(spec).result()
        session.submit(spec).result()               # a cache hit
        assert counts() == (1, 0)
        store.add_batch({"a": a[:500], "b": b[:500], "code": code[:500]})
        store.synopsis("b")                         # b's first fit
        session.submit(spec).result()               # a's refit
        assert counts() == (3, 1)


# --- batched QMC fallback ----------------------------------------------------

def test_batched_qmc_matches_per_query_loop(rng):
    """The shared-node batched fallback agrees with the old per-query loop;
    identical boxes share the exact node set, so agreement is tight there."""
    from repro.core.aqp import box_qmc_terms
    from repro.core.aqp_multid import _qmc_box_answers

    x = jnp.asarray(rng.normal(0, 1, (384, 2)).astype(np.float32))
    H = jnp.asarray([[0.16, 0.05], [0.05, 0.2]], jnp.float32)
    syn = KDESynopsis(x=x, H=H, n_source=384)
    same = [BoxQuery(op, (-1.0, -1.2), (1.2, 1.0), target=t)
            for op, t in (("count", 0), ("sum", 1), ("avg", 0))]
    got = _qmc_box_answers(syn, same)
    for q, g in zip(same, got):
        cnt, sm = box_qmc_terms(x, H, jnp.asarray(q.lo), jnp.asarray(q.hi),
                                target=q.target_index())
        want = {"count": float(cnt), "sum": float(sm),
                "avg": float(sm) / float(cnt)}[q.op]
        assert g == pytest.approx(want, rel=1e-4)

    mixed = [BoxQuery("count", tuple(lo), tuple(lo + rng.uniform(1.0, 2.5, 2)))
             for lo in [rng.uniform(-2.0, 0.0, 2) for _ in range(6)]]
    got = _qmc_box_answers(syn, mixed)
    for q, g in zip(mixed, got):
        cnt, _ = box_qmc_terms(x, H, jnp.asarray(q.lo), jnp.asarray(q.hi))
        assert g == pytest.approx(float(cnt), rel=0.08, abs=2.0)


def test_full_h_group_in_engine_close_to_closed_form(rng):
    """A full-H selector routes to the qmc path and lands near the
    diagonal-bandwidth closed-form answer for the same box."""
    x = rng.normal(0, 1, (512, 2)).astype(np.float32)
    store = TelemetryStore(capacity=512, seed=0)
    store.track_joint(("u", "v"))
    store.add_batch({"u": x[:, 0], "v": x[:, 1]})
    spec = AqpQuery("count", (Box(("u", "v"), (-1.5, -1.0), (1.0, 1.5)),))
    diag = store.engine().execute(spec, selector="plugin")[0]
    full = store.engine().execute(spec, selector="lscv_H")[0]
    assert diag.path == "box" and full.path == "qmc"
    assert full.estimate == pytest.approx(diag.estimate, rel=0.1)
