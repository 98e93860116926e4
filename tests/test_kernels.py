"""Per-kernel shape/dtype sweeps: every Pallas kernel vs its ref.py oracle
(interpret mode on CPU), plus the Appendix-A triangle index math."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # hypothesis is optional: property tests skip below
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.triangle import bx_to_ql, n_tri_tiles, ql_to_bx


def _check_triangle_roundtrip(bx):
    q, l = bx_to_ql(jnp.asarray([bx]))
    assert int(ql_to_bx(q, l)[0]) == bx
    assert 0 <= int(q[0]) <= int(l[0])


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(bx=st.integers(0, 10_000_000))
    def test_triangle_roundtrip(bx):
        _check_triangle_roundtrip(bx)
else:
    @pytest.mark.parametrize("bx", [0, 1, 2, 5, 977, 123_456, 10_000_000])
    def test_triangle_roundtrip(bx):
        _check_triangle_roundtrip(bx)


# (n, tile): below one tile (5, 64), several triangle tiles with a ragged
# last one (257, 1000, 2500), an exact multiple (384); from 1,024 on, a
# diagonal tile runs in 1,024-wide bands (2500, and 4096 / 3000 with two
# bands, the latter with a ragged off-diagonal column)
@pytest.mark.parametrize("n,tile", [(5, 128), (64, 128), (257, 128),
                                    (1000, 128), (384, 128), (2500, 1024),
                                    (4096, 2048), (3000, 2048)])
@pytest.mark.parametrize("kind", ["k4", "k6", "gauss"])
def test_pairwise_ksum(n, tile, kind):
    # dedicated per-case generator: K^(6) pair sums can cancel towards zero,
    # so the comparison needs deterministic data + a |sum|-scaled atol.
    local = np.random.default_rng(1234 + n)
    x = jnp.asarray(local.normal(0, 1, n).astype(np.float32))
    g = jnp.float32(0.4)
    a = ops.pairwise_scaled_ksum(x, g, kind=kind, tile=tile)
    b = ref.pairwise_scaled_ksum(x, g, kind)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                               atol=max(1e-5, 1e-6 * n))


@pytest.mark.parametrize("n,d", [(40, 2), (222, 4), (513, 8)])
def test_gh_fused(rng, n, d):
    x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    m0 = rng.normal(0, 1, (d, d)).astype(np.float32)
    m = jnp.asarray(0.1 * (m0 @ m0.T) + np.eye(d, dtype=np.float32))
    a = ops.gh_fused_sum(x, m, 0.31, 0.17, tile=64)
    b = ref.gh_fused_sum(x, m, 0.31, 0.17)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-4)


@pytest.mark.parametrize("n,d,n_h,tile", [
    pytest.param(*c, 128, id="-".join(map(str, c))) for c in (
        (100, 2, 5), (257, 3, 13),
        (100, 1, 5), (100, 3, 5), (256, 1, 13), (256, 2, 13), (256, 3, 13),
        (300, 1, 13), (300, 2, 13))] + [
    pytest.param(*c, 256, id="-".join(map(str, c)) + "-tile256") for c in (
        (300, 1, 5), (300, 1, 150), (600, 1, 13), (600, 2, 150))])
def test_lscv_grid(rng, n, d, n_h, tile):
    """The streaming triangular kernel against the dense oracle and the jnp
    streaming grid: 128-point tiles (n = 100, 257 and 300 ragged, 256 two
    whole tiles), and 256-point tiles of two lane groups (n = 300 ragged in
    the second, 600 over three tiles), on grids padded to whole blocks of
    eight points (5 to 8, 13 to 16, 150 to 152)."""
    from repro.core.lscv import _t_sums_streaming, exp_scales
    x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    m0 = rng.normal(0, 1, (d, d)).astype(np.float32)
    m = jnp.asarray(0.1 * (m0 @ m0.T) + np.eye(d, dtype=np.float32))
    scales = exp_scales(jnp.linspace(0.3, 2.0, n_h).astype(jnp.float32))
    hi, lo = ops.lscv_grid_sums(x, m, scales, 0.3, 0.2, tile=tile)
    a = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    b = ref.lscv_grid_sums(x, m, scales, 0.3, 0.2)
    c = _t_sums_streaming(x, m, scales, 0.3, 0.2, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-3)


def _tied_sample(rng, kind: str, n: int) -> np.ndarray:
    """Integers 16..2047 (seq_len's), four codes (model_id's), or half the
    rows on ten integers and half continuous."""
    if kind == "integers":
        x = rng.integers(16, 2048, n)
    elif kind == "codes":
        x = rng.integers(0, 4, n)
    else:
        x = np.where(rng.random(n) < 0.5, rng.integers(0, 10, n),
                     rng.normal(5.0, 3.0, n))
    return x.astype(np.float32)


@pytest.mark.parametrize("kind,n,n_h,tile", [
    pytest.param(k, 1024, 13, 128, id=k)
    for k in ("integers", "codes", "half-tied")] + [
    pytest.param("integers", 600, 150, 256, id="integers-600-150-tile256"),
    pytest.param("half-tied", 1024, 5, 256, id="half-tied-1024-5-tile256")])
def test_lscv_grid_weighted(rng, kind, n, n_h, tile):
    """Over a sample's distinct values with their multiplicities, the
    kernel's grid sums are the expanded sample's: the dense oracle's to its
    float32 rounding (<= 5.3e-6 of the sum here), the unweighted kernel's
    float32 pairs to theirs (<= 2.2e-7).  At 256-point tiles each tile of
    distinct values spans two lane groups."""
    from repro.core.lscv import distinct_values, exp_scales
    x = _tied_sample(rng, kind, n)
    ties = distinct_values(x)
    m = jnp.asarray([[1.0 / float(np.var(x, ddof=1))]], jnp.float32)
    scales = exp_scales(jnp.linspace(0.05, 1.0, n_h).astype(jnp.float32))
    hi, lo = ops.lscv_grid_sums(jnp.asarray(ties.values), m, scales, 0.3, 0.2,
                                tile=tile, weights=jnp.asarray(ties.weights),
                                tied=jnp.asarray(ties.tied))
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    full_hi, full_lo = ops.lscv_grid_sums(jnp.asarray(x), m, scales, 0.3, 0.2,
                                          tile=tile)
    full = np.asarray(full_hi, np.float64) + np.asarray(full_lo, np.float64)
    dense = ref.lscv_grid_sums(jnp.asarray(x)[:, None], m, scales, 0.3, 0.2)
    np.testing.assert_allclose(got, np.asarray(dense, np.float64), rtol=2e-5)
    np.testing.assert_allclose(got, full, rtol=1e-6)


def test_lscv_grid_counts_tied_pairs_exactly():
    """72,001,140 pairs at distance 0 (over 2^24, so one float32 would
    round them by 4) and the rest far apart: the sums are c_kk (1 - r)
    times that count, with r = 2 c_k / c_kk = 3, to 1e-12."""
    from repro.core.lscv import distinct_values, exp_scales, h_grid_for
    x = np.r_[np.zeros(12000), np.full(120, 1000.0)].astype(np.float32)
    ties = distinct_values(x)
    tied = 12000 * 11999 // 2 + 120 * 119 // 2
    assert tied > 2 ** 24 and int(np.float32(tied)) != tied
    assert float(ties.tied[0]) + float(ties.tied[1]) == tied
    m = jnp.asarray([[1.0 / float(np.var(x, ddof=1))]], jnp.float32)
    scales = exp_scales(h_grid_for(x.size, 1))
    hi, lo = ops.lscv_grid_sums(jnp.asarray(ties.values), m, scales, 0.375,
                                0.25, weights=jnp.asarray(ties.weights),
                                tied=jnp.asarray(ties.tied))
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    np.testing.assert_allclose(got, -0.5 * tied, rtol=1e-12)


@pytest.mark.parametrize("m,n,d", [(3, 17, 1), (65, 64, 2), (128, 500, 8)])
def test_kde_eval(rng, m, n, d):
    pts = jnp.asarray(rng.normal(0, 1, (m, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    a = ops.kde_eval(pts, x, jnp.float32(0.6), tile=64)
    b = ref.kde_eval(pts, x, jnp.float32(0.6))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-7)


def test_kernels_match_at_tile_boundaries(rng):
    """Exercise n == tile, n == tile+1, n == 2*tile-1 edge shapes (128 is
    the smallest tile the kernel takes)."""
    for n in [128, 129, 255, 256]:
        x = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
        a = ops.pairwise_scaled_ksum(x, jnp.float32(0.5), kind="k4", tile=128)
        b = ref.pairwise_scaled_ksum(x, jnp.float32(0.5), "k4")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=1e-5)


@pytest.mark.parametrize("n,q", [(17, 3), (64, 16), (500, 257)])
def test_aqp_batch_sums(rng, n, q):
    x = jnp.asarray(rng.normal(0, 2, n).astype(np.float32))
    a = jnp.asarray(rng.uniform(-4, 4, q).astype(np.float32))
    b = a + jnp.asarray(rng.uniform(0, 3, q).astype(np.float32))
    h = jnp.float32(0.5)
    c1, s1 = ops.aqp_batch_sums(x, h, a, b, tile=64, q_tile=16)
    c2, s2 = ref.aqp_batch_sums(x, h, a, b)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,q,d", [(17, 3, 2), (64, 16, 3), (500, 130, 4)])
def test_aqp_box_sums(rng, n, q, d):
    x = jnp.asarray(rng.normal(0, 1.5, (n, d)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.2, 0.8, d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(-3, 1, (q, d)).astype(np.float32))
    hi = lo + jnp.asarray(rng.uniform(0.2, 3, (q, d)).astype(np.float32))
    tgt = jnp.asarray(rng.integers(0, d, q), jnp.int32)
    c1, s1 = ops.aqp_box_sums(x, h, lo, hi, tgt, tile=64, q_tile=16)
    c2, s2 = ref.aqp_box_sums(x, h, lo, hi, tgt)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_aqp_box_sums_tile_boundaries(rng):
    """n == tile, n == tile+1, q == q_tile, q == q_tile+1 edge shapes."""
    d = 2
    h = jnp.asarray([0.4, 0.6], jnp.float32)
    for n, q in [(64, 16), (65, 17), (127, 15), (128, 16)]:
        x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
        lo = jnp.asarray(rng.uniform(-2, 0, (q, d)).astype(np.float32))
        hi = lo + 1.5
        tgt = jnp.asarray(rng.integers(0, d, q), jnp.int32)
        c1, s1 = ops.aqp_box_sums(x, h, lo, hi, tgt, tile=64, q_tile=16)
        c2, s2 = ref.aqp_box_sums(x, h, lo, hi, tgt)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_aqp_box_sums_empty_sample():
    """Zero grid iterations must not expose uninitialized output memory."""
    x = jnp.zeros((0, 3), jnp.float32)
    lo = jnp.zeros((2, 3), jnp.float32)
    hi = jnp.ones((2, 3), jnp.float32)
    tgt = jnp.zeros((2,), jnp.int32)
    c, s = ops.aqp_box_sums(x, jnp.ones((3,), jnp.float32), lo, hi, tgt)
    np.testing.assert_array_equal(np.asarray(c), 0.0)
    np.testing.assert_array_equal(np.asarray(s), 0.0)


@pytest.mark.parametrize("n,G,d,g_axis", [
    (17, 3, 2, 0), (64, 16, 3, 1), (65, 17, 2, 1), (127, 1, 4, 2),
    (128, 64, 2, 0), (500, 33, 3, 2), (200, 7, 1, 0)])
def test_aqp_grouped_sums(rng, n, G, d, g_axis):
    """Grouped kernel vs oracle across tile boundaries, G=1, d=1, odd G."""
    x = jnp.asarray(rng.normal(0, 1.5, (n, d)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.2, 0.8, d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(-3, 0, d).astype(np.float32))
    hi = lo + jnp.asarray(rng.uniform(1, 4, d).astype(np.float32))
    glo = jnp.asarray(np.sort(rng.uniform(-2, 2, G)).astype(np.float32))
    ghi = glo + 0.5
    for tgt in {0, g_axis, d - 1}:
        c1, s1 = ops.aqp_grouped_sums(x, h, lo, hi, glo, ghi, g_axis, tgt,
                                      tile=64, g_tile=16)
        c2, s2 = ref.aqp_grouped_sums(x, h, lo, hi, glo, ghi, g_axis, tgt)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, atol=1e-4)


def test_aqp_grouped_sums_matches_box_fanout(rng):
    """The factored pass answers exactly what per-category box fan-out
    answers: each category's box is the shared box with the group axis
    replaced by its window."""
    n, d, G, g_axis, tgt = 300, 3, 9, 1, 2
    x = jnp.asarray(rng.normal(0, 1.2, (n, d)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.3, 0.7, d).astype(np.float32))
    lo = jnp.asarray(rng.uniform(-2, 0, d).astype(np.float32))
    hi = lo + 2.5
    glo = jnp.asarray(np.arange(G, dtype=np.float32) - 4.0)
    ghi = glo + 0.8
    blo = jnp.tile(lo, (G, 1)).at[:, g_axis].set(glo)
    bhi = jnp.tile(hi, (G, 1)).at[:, g_axis].set(ghi)
    tgts = jnp.full((G,), tgt, jnp.int32)
    c1, s1 = ops.aqp_grouped_sums(x, h, lo, hi, glo, ghi, g_axis, tgt,
                                  tile=64, g_tile=16)
    c2, s2 = ops.aqp_box_sums(x, h, blo, bhi, tgts, tile=64, q_tile=16)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-3)


def test_aqp_grouped_sums_empty():
    """Zero grid iterations must not expose uninitialized output memory."""
    x = jnp.zeros((0, 2), jnp.float32)
    lo = jnp.zeros((2,), jnp.float32)
    hi = jnp.ones((2,), jnp.float32)
    glo = jnp.asarray([0.0, 1.0], jnp.float32)
    ghi = glo + 0.5
    c, s = ops.aqp_grouped_sums(x, jnp.ones((2,), jnp.float32), lo, hi,
                                glo, ghi, 0, 1)
    np.testing.assert_array_equal(np.asarray(c), 0.0)
    np.testing.assert_array_equal(np.asarray(s), 0.0)


@pytest.mark.parametrize("n,d,q,m", [
    (17, 2, 3, 33), (64, 3, 16, 64), (65, 2, 17, 129), (100, 1, 1, 200),
    (128, 4, 15, 256)])
def test_qmc_box_reduce(rng, n, d, q, m):
    """Fused QMC kernel vs dense oracle: non-tile-multiple n/m, q=1, d=1."""
    x = jnp.asarray(rng.normal(0, 1.0, (n, d)).astype(np.float32))
    nodes = jnp.asarray(rng.uniform(-2, 2, (m, d)).astype(np.float32))
    A = rng.normal(0, 0.3, (d, d))
    Hm = (A @ A.T + np.eye(d) * 0.5).astype(np.float32)
    h_inv = jnp.asarray(np.linalg.inv(Hm))
    log_norm = jnp.float32(-0.5 * d * np.log(2 * np.pi)
                           - 0.5 * np.linalg.slogdet(Hm)[1])
    lo = jnp.asarray(rng.uniform(-2, 0, (q, d)).astype(np.float32))
    hi = lo + 1.5
    tgt = jnp.asarray(rng.integers(0, d, q), jnp.int32)
    c1, s1 = ops.qmc_box_reduce(nodes, x, h_inv, log_norm, lo, hi, tgt,
                                tile=64, m_tile=32, q_tile=8)
    c2, s2 = ref.qmc_box_reduce(nodes, x, h_inv, log_norm, lo, hi, tgt)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-6)


def test_qmc_box_reduce_empty():
    """Zero grid iterations must not expose uninitialized output memory."""
    x = jnp.zeros((0, 2), jnp.float32)
    nodes = jnp.zeros((4, 2), jnp.float32)
    h_inv = jnp.eye(2, dtype=jnp.float32)
    lo = jnp.zeros((3, 2), jnp.float32)
    hi = jnp.ones((3, 2), jnp.float32)
    tgt = jnp.zeros((3,), jnp.int32)
    c, s = ops.qmc_box_reduce(nodes, x, h_inv, jnp.float32(0.0), lo, hi, tgt)
    np.testing.assert_array_equal(np.asarray(c), 0.0)
    np.testing.assert_array_equal(np.asarray(s), 0.0)


def test_env_tile_override(monkeypatch):
    """TILE/Q_TILE defaults resolve through env vars (real-TPU tuning)."""
    from repro import knobs
    from repro.kernels.tuning import env_int

    monkeypatch.setitem(
        knobs.KNOBS, "REPRO_TEST_TILE",
        knobs.Knob("REPRO_TEST_TILE", "int", 128, "scratch knob for this test"))
    monkeypatch.setenv("REPRO_TEST_TILE", "512")
    assert env_int("REPRO_TEST_TILE", 128) == 512
    monkeypatch.delenv("REPRO_TEST_TILE")
    assert env_int("REPRO_TEST_TILE", 128) == 128
    monkeypatch.setenv("REPRO_TEST_TILE", "not-a-number")
    with pytest.raises(ValueError, match="positive integer"):
        env_int("REPRO_TEST_TILE", 128)
    monkeypatch.setenv("REPRO_TEST_TILE", "-4")
    with pytest.raises(ValueError, match="positive integer"):
        env_int("REPRO_TEST_TILE", 128)
    with pytest.raises(KeyError, match="unregistered"):
        env_int("REPRO_NOT_REGISTERED_TILE", 128)


def test_aqp_batch_sums_empty_sample():
    """Zero grid iterations must not expose uninitialized output memory."""
    x = jnp.zeros((0,), jnp.float32)
    a = jnp.asarray([0.0, 1.0], jnp.float32)
    b = jnp.asarray([1.0, 2.0], jnp.float32)
    c, s = ops.aqp_batch_sums(x, jnp.float32(0.5), a, b)
    np.testing.assert_array_equal(np.asarray(c), 0.0)
    np.testing.assert_array_equal(np.asarray(s), 0.0)


def test_kernel_erf_within_stated_bound():
    """The in-kernel f32 erf stays within ERF_ABS_ERR of the float64 erf
    over the whole f32 range the closed forms feed it."""
    import math

    from repro.kernels.erf import ERF_ABS_ERR, erf

    x = np.linspace(-9.0, 9.0, 400_001).astype(np.float32)
    got = np.asarray(erf(jnp.asarray(x)), np.float64)
    want = np.asarray([math.erf(float(v)) for v in x])
    assert np.max(np.abs(got - want)) <= ERF_ABS_ERR
    assert got[0] == -1.0 and got[-1] == 1.0
