"""AQP queries on KDE synopses (paper §4.3, eqs. 9-11): closed form vs
quadrature, accuracy vs exact answers, invariants, mergeability."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # hypothesis is optional: property tests skip below
    HAVE_HYPOTHESIS = False

from repro.core import (KDESynopsis, count_1d, count_1d_numeric, count_box_diag,
                        sum_1d, sum_1d_numeric)
from repro.data import TelemetryStore


def test_closed_form_equals_quadrature(rng):
    x = jnp.asarray(rng.normal(0, 2, 500).astype(np.float32))
    h = jnp.float32(0.3)
    a, b = jnp.float32(-1.0), jnp.float32(2.5)
    assert float(count_1d(x, h, a, b)) == pytest.approx(
        float(count_1d_numeric(x, h, a, b)), rel=1e-3)
    assert float(sum_1d(x, h, a, b)) == pytest.approx(
        float(sum_1d_numeric(x, h, a, b)), rel=2e-3)


def test_count_accuracy_vs_exact(rng):
    data = rng.normal(10.0, 3.0, 20000).astype(np.float32)
    syn = KDESynopsis.fit(jnp.asarray(data), selector="plugin", max_sample=2048)
    for a, b in [(7.0, 13.0), (4.0, 10.0), (12.0, 20.0)]:
        approx = float(syn.count(a, b))
        exact = float(((data >= a) & (data <= b)).sum())
        assert approx == pytest.approx(exact, rel=0.08), (a, b)


def test_sum_avg_accuracy(rng):
    data = rng.gamma(4.0, 2.0, 20000).astype(np.float32)
    syn = KDESynopsis.fit(jnp.asarray(data), selector="plugin", max_sample=2048)
    sel = (data >= 5.0) & (data <= 12.0)
    assert float(syn.sum(5.0, 12.0)) == pytest.approx(float(data[sel].sum()), rel=0.12)
    assert float(syn.avg(5.0, 12.0)) == pytest.approx(float(data[sel].mean()), rel=0.05)


def _check_count_bounds_and_monotonicity(seed, b):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, 256).astype(np.float32))
    h = jnp.float32(0.4)
    c1 = float(count_1d(x, h, jnp.float32(-10.0), jnp.float32(b)))
    c2 = float(count_1d(x, h, jnp.float32(-10.0), jnp.float32(b + 0.5)))
    assert -1e-3 <= c1 <= 256 * (1 + 1e-4)
    assert c2 >= c1 - 1e-4                       # monotone in the upper bound


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 50), b=st.floats(-1.0, 3.0))
    def test_count_bounds_and_monotonicity(seed, b):
        _check_count_bounds_and_monotonicity(seed, b)
else:
    @pytest.mark.parametrize("seed,b", [(0, -1.0), (17, 0.3), (50, 3.0)])
    def test_count_bounds_and_monotonicity(seed, b):
        _check_count_bounds_and_monotonicity(seed, b)


def test_multid_box_count(rng):
    data = rng.normal(0, 1, (8000, 2)).astype(np.float32)
    h = jnp.asarray([0.15, 0.15], jnp.float32)
    approx = float(count_box_diag(jnp.asarray(data), h,
                                  jnp.asarray([-1.0, -1.0], jnp.float32),
                                  jnp.asarray([1.0, 1.0], jnp.float32)))
    exact = float(((np.abs(data) <= 1.0).all(axis=1)).sum())
    assert approx == pytest.approx(exact, rel=0.08)


def test_lscv_H_synopsis_box(rng):
    data = rng.normal(0, 1, (3000, 2)).astype(np.float32)
    data[:, 1] = 0.5 * data[:, 0] + 0.9 * data[:, 1]
    syn = KDESynopsis.fit(jnp.asarray(data), selector="lscv_H", max_sample=512)
    approx = float(syn.count_box([-1.0, -1.0], [1.0, 1.0]))
    inbox = ((data >= -1) & (data <= 1)).all(axis=1).sum()
    assert approx == pytest.approx(float(inbox), rel=0.2)


def test_telemetry_store_and_merge(rng):
    s1 = TelemetryStore(capacity=512, seed=1)
    s2 = TelemetryStore(capacity=512, seed=2)
    a = rng.normal(0, 1, 4000).astype(np.float32)
    b = rng.normal(2, 1, 4000).astype(np.float32)
    s1.add_batch({"loss": a})
    s2.add_batch({"loss": b})
    merged = s1.merge(s2)
    frac = merged.fraction("loss", -10.0, 1.0, selector="silverman")
    exact = float((np.concatenate([a, b]) <= 1.0).mean())
    assert frac == pytest.approx(exact, abs=0.08)
    assert merged.columns["loss"].n_seen == 8000


# --- deterministic closed-form + batched-engine tests ----------------------

def test_closed_forms_vs_trapezoid_of_kde_eval(rng):
    """eqs. 9-10 closed forms vs direct trapezoid quadrature of kde_eval."""
    from repro.core import kde_eval

    x = jnp.asarray(rng.normal(1.0, 1.5, 400).astype(np.float32))
    h = jnp.float32(0.35)
    for a, b in [(-2.0, 0.5), (0.0, 4.0), (-6.0, 6.0)]:
        grid = jnp.linspace(a, b, 2001)
        f = kde_eval(grid, x, h)
        n = x.shape[0]
        want_count = n * float(jnp.trapezoid(f, grid))
        want_sum = n * float(jnp.trapezoid(grid * f, grid))
        assert float(count_1d(x, h, jnp.float32(a), jnp.float32(b))) == \
            pytest.approx(want_count, rel=1e-4), (a, b)
        assert float(sum_1d(x, h, jnp.float32(a), jnp.float32(b))) == \
            pytest.approx(want_sum, rel=1e-4, abs=1e-3), (a, b)


def test_degenerate_ranges(rng):
    x = jnp.asarray(rng.normal(0, 1, 300).astype(np.float32))
    h = jnp.float32(0.4)
    # a == b: zero-measure range (sum_1d may carry fp32 roundoff from the
    # two-term Phi/phi cancellation, so approx rather than exact zero)
    assert float(count_1d(x, h, jnp.float32(0.7), jnp.float32(0.7))) == 0.0
    assert float(sum_1d(x, h, jnp.float32(0.7), jnp.float32(0.7))) == \
        pytest.approx(0.0, abs=1e-6)
    # empty intersection: range far outside the support
    assert float(count_1d(x, h, jnp.float32(50.0), jnp.float32(60.0))) == \
        pytest.approx(0.0, abs=1e-4)
    assert float(sum_1d(x, h, jnp.float32(50.0), jnp.float32(60.0))) == \
        pytest.approx(0.0, abs=1e-3)


def test_avg_of_degenerate_range_is_finite(rng):
    data = rng.normal(0, 1, 5000).astype(np.float32)
    syn = KDESynopsis.fit(jnp.asarray(data), selector="plugin", max_sample=512)
    assert np.isfinite(float(syn.avg(0.3, 0.3)))
    assert np.isfinite(float(syn.avg(80.0, 90.0)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_batched_engine_matches_query_loop(rng, backend):
    from repro.core import Query, QueryBatch

    data = rng.gamma(4.0, 2.0, 30000).astype(np.float32)
    syn = KDESynopsis.fit(jnp.asarray(data), selector="plugin", max_sample=1024)
    ops = ["count", "sum", "avg"]
    lo, hi = float(data.min()), float(data.max())
    queries = []
    for i in range(1001):                 # >= 1000, non-multiple of tile sizes
        a = float(rng.uniform(lo, hi))
        queries.append(Query(ops[i % 3], a, float(rng.uniform(a, hi))))
    queries.append(Query("count", 5.0, 5.0))          # degenerate
    queries.append(Query("avg", hi + 10, hi + 20))    # empty intersection

    got = QueryBatch(queries).run(syn, backend=backend)
    fns = {"count": syn.count, "sum": syn.sum, "avg": syn.avg}
    want = np.asarray([float(fns[q.op](q.a, q.b)) for q in queries])
    if backend == "jnp":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
        return
    # The Pallas kernel evaluates erf with its own f32 approximation
    # (kernels/erf.py), the reference with jax's; each is within
    # ERF_ABS_ERR of the true erf.  Per sample, the Phi difference of the
    # two paths then differs by at most 2 * ERF_ABS_ERR (two erf terms,
    # each halved, on each side) plus the f32 rounding of Phi near 1 on
    # the reference side (2^-23).  Summed over the sample and scaled to the
    # relation, COUNT may differ by n_source times that, SUM by the same
    # times max|x|, and AVG = SUM / COUNT by the propagated ratio error.
    from repro.kernels.erf import ERF_ABS_ERR

    cnt_tol = syn.n_source * (2 * ERF_ABS_ERR + 2.0 ** -23)
    sum_tol = cnt_tol * float(np.max(np.abs(np.asarray(syn.x))))
    tol = []
    for q, w in zip(queries, want):
        if q.op == "count":
            tol.append(cnt_tol)
        elif q.op == "sum":
            tol.append(sum_tol)
        else:
            cnt = float(syn.count(q.a, q.b))
            tol.append((sum_tol + abs(w) * cnt_tol) / max(cnt, 1e-3))
    # plus f32 summation order (the kernel sums in tiles)
    bound = np.asarray(tol) + 1e-5 * np.abs(want)
    bad = np.abs(got - want) > bound
    assert not bad.any(), (np.flatnonzero(bad), got[bad], want[bad],
                           bound[bad])


def test_query_batch_groups_columns(rng):
    from repro.core import Query, QueryBatch

    d1 = rng.normal(0, 1, 8000).astype(np.float32)
    d2 = rng.normal(5, 2, 8000).astype(np.float32)
    synopses = {
        "a": KDESynopsis.fit(jnp.asarray(d1), selector="plugin", max_sample=512),
        "b": KDESynopsis.fit(jnp.asarray(d2), selector="plugin", max_sample=512),
    }
    queries = [Query("count", -1, 1, column="a"), Query("sum", 3, 7, column="b"),
               Query("avg", -2, 0, column="a"), Query("count", 4, 6, column="b")]
    batch = QueryBatch(queries)
    assert sorted(batch.columns) == ["a", "b"]
    got = batch.run(synopses)
    for q, ans in zip(queries, got):
        syn = synopses[q.column]
        want = float({"count": syn.count, "sum": syn.sum, "avg": syn.avg}[q.op](q.a, q.b))
        assert ans == pytest.approx(want, rel=1e-5, abs=1e-5)


def test_query_rejects_unknown_op():
    from repro.core import Query

    with pytest.raises(ValueError):
        Query("median", 0.0, 1.0)


def test_query_batch_rejects_column_tags_against_bare_synopsis(rng):
    from repro.core import Query, QueryBatch

    data = rng.normal(0, 1, 2000).astype(np.float32)
    syn = KDESynopsis.fit(jnp.asarray(data), selector="plugin", max_sample=256)
    with pytest.raises(ValueError, match="single synopsis"):
        QueryBatch([Query("count", 0, 1, column="latency")]).run(syn)
