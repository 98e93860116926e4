"""PLUGIN bandwidth selector vs the paper's sequential implementation and
statistical invariants (paper §4.4 eqs. 12-19)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # hypothesis is optional: property tests skip below
    HAVE_HYPOTHESIS = False

from repro.core import plugin_bandwidth, plugin_bandwidth_sequential
from repro.core.binned import binned_plugin_bandwidth


def test_matches_sequential_oracle(rng):
    x = rng.normal(1.0, 2.0, 400).astype(np.float32)
    h_jax = float(plugin_bandwidth(jnp.asarray(x)).h)
    h_seq = plugin_bandwidth_sequential(x)
    assert abs(h_jax - h_seq) / h_seq < 1e-3


# 9,000 points span three 4,096 tiles of the pair-sum kernel, the last
# ragged
@pytest.mark.parametrize("n,rtol", [(700, 1e-3), (9000, 1e-4)])
def test_pallas_backend_matches(rng, n, rtol):
    x = rng.normal(0.0, 1.0, n).astype(np.float32)
    a = float(plugin_bandwidth(jnp.asarray(x)).h)
    b = float(plugin_bandwidth(jnp.asarray(x), backend="pallas").h)
    assert abs(a - b) / a < rtol


def test_normal_reference_magnitude(rng):
    # For N(0,1), h_PLUGIN should be within a small factor of Silverman's rule.
    n = 2048
    x = rng.normal(0.0, 1.0, n).astype(np.float32)
    h = float(plugin_bandwidth(jnp.asarray(x)).h)
    silverman = 1.06 * n ** -0.2
    assert 0.3 * silverman < h < 2.0 * silverman


def _check_scale_equivariance(scale, shift, seed):
    """h(a*X + b) == a * h(X): bandwidths are scale-equivariant."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, 256).astype(np.float32)
    h1 = float(plugin_bandwidth(jnp.asarray(x)).h)
    h2 = float(plugin_bandwidth(jnp.asarray(scale * x + shift, dtype=jnp.float32)).h)
    assert h2 == pytest.approx(scale * h1, rel=5e-3)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(scale=st.floats(0.1, 10.0), shift=st.floats(-5.0, 5.0),
           seed=st.integers(0, 100))
    def test_scale_equivariance(scale, shift, seed):
        _check_scale_equivariance(scale, shift, seed)
else:
    @pytest.mark.parametrize("scale,shift,seed",
                             [(0.1, -5.0, 0), (1.0, 0.0, 7), (10.0, 5.0, 42)])
    def test_scale_equivariance(scale, shift, seed):
        _check_scale_equivariance(scale, shift, seed)


def test_permutation_invariance(rng):
    x = rng.normal(0.0, 1.5, 333).astype(np.float32)
    h1 = float(plugin_bandwidth(jnp.asarray(x)).h)
    h2 = float(plugin_bandwidth(jnp.asarray(rng.permutation(x))).h)
    assert h1 == pytest.approx(h2, rel=1e-4)


def test_binned_close_to_exact(rng):
    x = rng.normal(0.0, 1.0, 4096).astype(np.float32)
    h_exact = float(plugin_bandwidth(jnp.asarray(x)).h)
    h_binned = float(binned_plugin_bandwidth(jnp.asarray(x)))
    assert abs(h_binned - h_exact) / h_exact < 0.02


def test_intermediates_match_paper_constants(rng):
    """g1/g2/psi plumbing: check signs and orderings the formulas imply."""
    x = rng.normal(0.0, 1.0, 512).astype(np.float32)
    r = plugin_bandwidth(jnp.asarray(x))
    assert float(r.psi8) > 0          # eq. 14: positive by construction
    assert float(r.psi6) < 0          # Psi6 < 0 for smooth densities
    assert float(r.psi4) > 0          # Psi4 > 0
    assert 0 < float(r.g1) < 2.0
    assert 0 < float(r.g2) < 2.0
    assert 0 < float(r.h) < 1.0
