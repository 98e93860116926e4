"""LSCV_h / LSCV_H selectors: float64 oracles, the §4.5 reformulation
equivalence, SPD constraints, Nelder-Mead behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import g_of_H, lscv_H, lscv_h
from repro.core.lscv import g_of_h_sequential, h0_start, h_start, matrix_sqrt
from repro.core.nelder_mead import minimize as nm_minimize


def test_g_of_h_matches_float64_oracle(rng):
    x = rng.normal(0.0, 1.0, (150, 3)).astype(np.float32)
    res = lscv_h(jnp.asarray(x), n_h=7)
    for i in [0, 3, 6]:
        oracle = g_of_h_sequential(x, float(res.h_grid[i]))
        assert float(res.g_values[i]) == pytest.approx(oracle, rel=2e-3)


def test_store_s_fused_pallas_agree(rng):
    """Paper two-phase (store S), streaming fused, and the Pallas kernels all
    evaluate the same objective (the §4.5 claim: same values, fewer ops)."""
    x = rng.normal(0.0, 2.0, (220, 2)).astype(np.float32)
    a = lscv_h(jnp.asarray(x), n_h=20, store_s=True)
    b = lscv_h(jnp.asarray(x), n_h=20, store_s=False)
    c = lscv_h(jnp.asarray(x), n_h=20, backend="pallas")
    np.testing.assert_allclose(a.g_values, b.g_values, rtol=3e-4)
    np.testing.assert_allclose(a.g_values, c.g_values, rtol=3e-4)
    assert float(a.h) == float(b.h) == float(c.h)


def test_pallas_argmin_is_float64s_on_a_near_tie():
    """Loss-like data whose two best grid points differ by 5.7e-8 of g: the
    float32 g values pick the wrong one; the Pallas fit, which takes the
    argmin on its float32 pairs, picks float64's."""
    x = np.random.default_rng(120).gamma(3.0, 0.7, 1024).astype(np.float32)
    res = lscv_h(jnp.asarray(x), backend="pallas")
    xd = x.astype(np.float64)
    n, sigma = xd.size, np.std(xd, ddof=1)
    i, j = np.triu_indices(n, 1)
    s = ((xd[i] - xd[j]) / sigma) ** 2
    grid = np.asarray(res.h_grid, np.float64)
    c_k, c_kk = (2 * np.pi) ** -0.5 / sigma, (4 * np.pi) ** -0.5 / sigma
    g = np.asarray([(2.0 / n ** 2 * np.sum(c_kk * np.exp(-s / (4 * h * h))
                                           - 2 * c_k * np.exp(-s / (2 * h * h)))
                     + c_kk / n) / h for h in grid])
    assert np.argmin(np.asarray(res.g_values)) != np.argmin(g)
    assert float(res.h) == float(res.h_grid[np.argmin(g)])


def test_pallas_and_jnp_pick_one_grid_point_on_a_bimodal_sample():
    """`latency_ms`-shaped data (80% N(40, 8), 20% N(160, 30)), 1,024 rows,
    on the served 150-point grid: the Pallas kernel's pair sums and the jnp
    streaming sums give g to float32 rounding and pick the same grid
    index."""
    rng = np.random.default_rng(1900)
    n = 1024
    x = np.where(rng.random(n) < 0.8, rng.normal(40.0, 8.0, n),
                 rng.normal(160.0, 30.0, n)).astype(np.float32)
    got = lscv_h(jnp.asarray(x), backend="pallas")
    want = lscv_h(jnp.asarray(x))
    assert got.h_grid.shape == (150,)
    np.testing.assert_allclose(got.g_values, want.g_values, rtol=3e-4)
    grid = np.asarray(want.h_grid)
    assert (np.flatnonzero(grid == float(got.h)).tolist()
            == np.flatnonzero(grid == float(want.h)).tolist())


def test_inv_h_is_the_exponent_scales_bandwidth():
    """g's 1 / h is 2 sqrt(-scale) of the float32 scale the sums used, to
    far below a float32 rounding: the pair off float64 by < 1e-13."""
    from repro.core.lscv import _inv_h, exp_scales, h_grid_for
    scales = exp_scales(h_grid_for(32768, 1))
    hi, lo = _inv_h(scales)
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    want = 2.0 * np.sqrt(-np.asarray(scales, np.float64))
    assert np.max(np.abs(got / want - 1.0)) < 1e-13
    assert np.max(np.abs(np.asarray(hi, np.float64) / want - 1.0)) > 1e-9


def _tied(kind: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(17)
    if kind == "integers":                  # seq_len's 16..2047
        x = rng.integers(16, 2048, n)
    elif kind == "codes":                   # model_id's four codes
        x = rng.integers(0, 4, n)
    else:                                   # half on ten integers
        x = np.where(rng.random(n) < 0.5, rng.integers(0, 10, n),
                     rng.normal(5.0, 3.0, n))
    return x.astype(np.float32)


@pytest.mark.parametrize("kind", ["integers", "codes", "half-tied"])
def test_lscv_h_over_distinct_values_picks_the_same_h(kind):
    """The grid sums over a tied sample's distinct values with their
    multiplicities pick the grid point the sums over every pair pick, with
    sigma from the whole sample: the same h_j sigma_j, and g within float32
    rounding of its largest value."""
    from repro.core.lscv import distinct_values
    x = jnp.asarray(_tied(kind, 2048))
    full = lscv_h(x, n_h=40, backend="pallas")
    ties = distinct_values(np.asarray(x))
    got = lscv_h(x, n_h=40, backend="pallas", ties=ties)
    assert float(got.h) == float(full.h)
    assert float(got.sigma[0, 0]) == float(full.sigma[0, 0])
    g = np.asarray(full.g_values)
    np.testing.assert_allclose(got.g_values, g, rtol=0,
                               atol=1e-6 * np.max(np.abs(g)))


def test_fit_collapses_only_where_ties_pay():
    """A served LSCV_h fit sums over distinct values where u_p <= n / 2 and
    keeps the call over every pair elsewhere: a continuous axis's bandwidth
    is bit-identical to `lscv_h` on the column, a 4-code axis's is the
    same grid point from 128 points."""
    from repro.core.aqp import KDESynopsis
    rng = np.random.default_rng(5)
    x = np.stack([rng.normal(0.0, 2.0, 512),
                  rng.integers(0, 4, 512)], axis=1).astype(np.float32)
    syn = KDESynopsis.fit(x, selector="lscv_h", max_sample=512,
                          backend="pallas")
    assert syn.grid_rows == (512, 128)
    cont = lscv_h(jnp.asarray(x[:, 0]), backend="pallas")
    assert np.asarray(syn.grid_h)[0] == np.asarray(cont.h)
    assert np.asarray(syn.h)[0] == np.asarray(
        cont.h * jnp.sqrt(cont.sigma[0, 0]))
    codes = lscv_h(jnp.asarray(x[:, 1]), backend="pallas")
    assert np.asarray(syn.grid_h)[1] == np.asarray(codes.h)
    assert KDESynopsis.fit(x, selector="lscv_h", max_sample=512,
                           backend="jnp").grid_rows == (512, 512)


def test_h0_1d_is_silverman():
    # eq. (28) for d=1 must reduce to (4/3)^(1/5) n^(-1/5)
    n = 1000
    assert h0_start(n, 1) == pytest.approx((4.0 / 3.0) ** 0.2 * n ** -0.2, rel=1e-6)


def test_optimum_interior(rng):
    x = rng.normal(0.0, 1.0, 400).astype(np.float32)
    res = lscv_h(jnp.asarray(x))
    # argmin not on the search boundary (eq. 29 interval is adequate)
    assert float(res.h_grid[0]) < float(res.h) < float(res.h_grid[-1])


def test_scale_equivariance_lscv_h(rng):
    x = rng.normal(0.0, 1.0, 300).astype(np.float32)
    h1 = float(lscv_h(jnp.asarray(x)).h)
    h2 = float(lscv_h(jnp.asarray(3.0 * x)).h)
    # the Mahalanobis kernel whitens by Sigma, so h is scale-invariant
    assert h2 == pytest.approx(h1, rel=5e-2)


def test_g_of_H_oracle(rng):
    x = rng.normal(0.0, 1.0, (120, 2)).astype(np.float32)
    H = np.array([[0.2, 0.03], [0.03, 0.3]], np.float32)

    # float64 numpy oracle of eq. (32)
    import math
    xd = x.astype(np.float64)
    Hd = H.astype(np.float64)
    n, d = xd.shape
    det = np.linalg.det(Hd)
    inv = np.linalg.inv(Hd)
    acc = 0.0
    for i in range(n):
        for j in range(i + 1, n):
            u = xd[i] - xd[j]
            s = u @ inv @ u
            acc += ((4 * math.pi) ** (-d / 2) * det ** -0.5 * math.exp(-0.25 * s)
                    - 2 * (2 * math.pi) ** (-d / 2) * det ** -0.5 * math.exp(-0.5 * s))
    oracle = 2.0 / (n * n) * acc + 2.0 ** (-d) * math.pi ** (-d / 2) * det ** -0.5 / n

    got = float(g_of_H(jnp.asarray(x), jnp.asarray(H)))
    got_pallas = float(g_of_H(jnp.asarray(x), jnp.asarray(H), backend="pallas"))
    assert got == pytest.approx(oracle, rel=2e-3)
    assert got_pallas == pytest.approx(oracle, rel=2e-3)


def test_lscv_H_improves_and_is_spd(rng):
    x = rng.normal(0.0, 1.0, (200, 2)).astype(np.float32)
    x[:, 1] = 0.6 * x[:, 0] + 0.8 * x[:, 1]
    res = lscv_H(jnp.asarray(x), max_iter=80)
    g_start = float(g_of_H(jnp.asarray(x), res.H_start))
    assert float(res.g) <= g_start + 1e-7          # NM never worsens
    w = np.linalg.eigvalsh(np.asarray(res.H, np.float64))
    assert (w > 0).all()                           # SPD by construction


def test_h_start_matches_eq37(rng):
    x = rng.normal(0.0, 2.0, (500, 3)).astype(np.float32)
    n, d = x.shape
    H0 = np.asarray(h_start(jnp.asarray(x)), np.float64)
    sigma = np.cov(x.astype(np.float64), rowvar=False)
    expect = (4.0 / (d + 2)) ** (1.0 / (d + 4)) * n ** (-1.0 / (d + 4)) * \
        _sqrtm(sigma)
    np.testing.assert_allclose(H0, expect, rtol=2e-2)


def _sqrtm(a):
    w, v = np.linalg.eigh(a)
    return (v * np.sqrt(w)) @ v.T


def test_nelder_mead_on_rosenbrock():
    def rosen(p):
        return (1 - p[0]) ** 2 + 100.0 * (p[1] - p[0] ** 2) ** 2

    res = nm_minimize(rosen, jnp.asarray([-1.2, 1.0], jnp.float32), max_iter=400,
                      init_scale=0.5)
    assert float(res.fun) < 1e-2
    np.testing.assert_allclose(np.asarray(res.x), [1.0, 1.0], atol=0.15)
