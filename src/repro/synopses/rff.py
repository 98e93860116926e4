"""Random-Fourier-feature density synopsis — the sublinear full-H backend.

Gallego et al. 2022 ("Fast Kernel Density Estimation with Density Matrices
and Random Fourier Features"): Bochner's theorem writes the anisotropic
Gaussian kernel k(x, y) = exp(-1/2 (x-y)^T H^-1 (x-y)) as the expectation
of cos features under its spectral density, which for this kernel is
N(0, H^-1).  Drawing D frequencies

    w_j = L^-T z_j,   H = L L^T (Cholesky),  z_j ~ N(0, I_d)

gives Cov(w) = (L L^T)^-1 = H^-1 exactly — the full anisotropic bandwidth
is honored, not a diagonal approximation.  With phases b_j ~ U[0, 2pi) the
feature map phi(x) = sqrt(2/D) cos(Wx + b) satisfies
E[phi(x) . phi(y)] = k(x, y), so the whole n-row sample compresses into ONE
D-vector

    z_bar = (1/n) sum_i phi(X_i)                  (fit: O(n * D), once)

and the density estimate is a dot product independent of n:

    f^(p) = norm * (phi(p) . z_bar),              (eval: O(D) per point)
    norm  = (2 pi)^(-d/2) |H|^(-1/2)

The Monte-Carlo feature average can go slightly negative where the true
density is ~0; evals are deliberately NOT clipped at zero.  The noise is
zero-mean, so the quasi-MC box integrals downstream cancel it — clipping
would rectify it into a positive bias that grows with the integration
volume (measured: ~40% count inflation over a wide box at D=2048, vs <1%
unclipped).  Callers that need a nonnegative density for display should
clip at the surface, not here.  The fitted state is a fixed-size
array triple (W, b, z), so it rides the PR 5 checkpoint format untouched
and shards trivially.

Everything is seeded: the same (seed, n_features, H) always draws the same
frequencies, so a checkpoint round-trip reproduces densities bit-for-bit
(test-enforced) and cross-host fits agree.

Confidence intervals: the exact path's `qmc_subsample_se` re-evaluates the
KDE on K sample chunks — O(n * m), which would erase the sublinear win.
Here the natural independent replicates are the *features*: splitting the D
features into B blocks gives B unbiased density estimates per point, and
batch-means over the per-block query answers yields a Student-t SE at
O(m * D) total — same cost order as the estimate itself
(`block_densities`, consumed by `aqp_multid.qmc_rff_se`).

Accuracy profile (measured, 2-d, n=50k): the estimator is unbiased over the
(W, b) draw, but a *single* draw carries spatially correlated noise whose
box-integral error shrinks only as 1/sqrt(D) and grows as the bandwidth
shrinks (smaller H -> higher frequencies).  At H = 0.1 * cov, D=2048, the
per-seed COUNT error over a wide box has sd ~ 25% — and the feature-block
SE tracks it (measured SE 6-13k against a true seed-to-seed sd of 9.3k), so
reported CIs stay honest even when a draw lands far out.  The engine's
probe gate additionally catches pointwise degradation and falls back to
exact.  Use wider bandwidths (>= 0.2 * cov) or larger D where tight boxes
matter.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import DensitySynopsis, register

# feature-map fit is chunked over sample rows so memory stays
# O(chunk * n_features) even for 200k+ row reservoirs
FIT_CHUNK = 4096

# f32 matmul passes: XLA on a TPU defaults f32 dots to one bf16 pass, which
# would put ~1e-2 rad of phase error on |w.x| ~ 10^2 projections
_F32 = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("chunk",))
def _mean_cos(x: jax.Array, w: jax.Array, b: jax.Array,
              chunk: int = FIT_CHUNK) -> jax.Array:
    """(1/n) sum_i cos(W x_i + b) over sample rows, scanned in chunks."""
    n, d = x.shape
    c = min(chunk, max(n, 1))
    pad = (-n) % c
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    mask = (jnp.arange(n + pad) < n).astype(w.dtype)

    def body(acc, args):
        xc, mc = args
        proj = jnp.dot(xc, w.T, precision=_F32) + b[None, :]   # (c, D)
        return acc + jnp.sum(jnp.cos(proj) * mc[:, None], axis=0), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((w.shape[0],), w.dtype),
        (xp.reshape(-1, c, d), mask.reshape(-1, c)))
    return acc / jnp.maximum(n, 1)


@partial(jax.jit, static_argnames=("n_blocks",))
def _block_densities(points: jax.Array, w: jax.Array, b: jax.Array,
                     z: jax.Array, norm: jax.Array,
                     n_blocks: int) -> jax.Array:
    """Per-feature-block densities, (n_blocks, m): block k rescales its
    partial dot by D / |block| so each block is an unbiased estimate of the
    same density — the batch-means replicates behind `qmc_rff_se`."""
    D = w.shape[0]
    db = D // n_blocks
    wb = w[:db * n_blocks].reshape(n_blocks, db, -1)
    bb = b[:db * n_blocks].reshape(n_blocks, db)
    zb = z[:db * n_blocks].reshape(n_blocks, db)
    rescale = jnp.asarray(D / db, w.dtype)

    def one(args):
        wk, bk, zk = args
        proj = jnp.dot(points, wk.T, precision=_F32) + bk[None, :]
        raw = jnp.dot(jnp.cos(proj), zk, precision=_F32)
        return norm * rescale * raw

    # lax.map, not vmap: vmap would materialise the full (B, m, D/B) cos
    # tensor at once — the whole point of blocking is bounded memory
    return jax.lax.map(one, (wb, bb, zb))


@register("rff")
class RFFSynopsis(DensitySynopsis):
    """Fitted RFF state: frequencies W (D, d), phases b (D,), and the
    scaled sample feature mean z (D,) with the 2/D feature scale folded in,
    so eval is  f^(p) = norm * (cos(W p + b) . z) — unclipped, see the
    module docstring."""

    def __init__(self, w, b, z, norm: float, n_fitted: int, seed: int):
        self.w = w
        self.b = b
        self.z = z
        self.norm = float(norm)
        self.n_fitted = int(n_fitted)
        self.seed = int(seed)
        self.probe_rel_err = float("nan")   # set by the engine's gate

    @property
    def n_features(self) -> int:
        return int(self.w.shape[0])

    @property
    def d(self) -> int:
        return int(self.w.shape[1])

    @classmethod
    def fit(cls, sample, H, n_features: int = 2048,
            seed: int = 0) -> "RFFSynopsis":
        """One-shot fit against the retained rows.  O(n * n_features); the
        result never touches the sample again."""
        x = jnp.asarray(sample, jnp.float32)
        if x.ndim == 1:
            x = x[:, None]
        n, d = x.shape
        H64 = np.asarray(H, np.float64).reshape(d, d)
        L = np.linalg.cholesky(H64)
        sign, logdet = np.linalg.slogdet(H64)
        if sign <= 0:
            raise ValueError("bandwidth matrix H must be positive definite")
        norm = math.exp(-d / 2.0 * math.log(2.0 * math.pi) - 0.5 * logdet)
        key_w, key_b = jax.random.split(jax.random.PRNGKey(seed))
        zeta = np.asarray(
            jax.random.normal(key_w, (n_features, d), jnp.float32),
            np.float64)
        # w_j = L^-T zeta_j  =>  Cov(w) = H^-1 (anisotropy honored)
        w = np.linalg.solve(L.T, zeta.T).T.astype(np.float32)
        b = jax.random.uniform(key_b, (n_features,), jnp.float32,
                               0.0, 2.0 * math.pi)
        w = jnp.asarray(w)
        z = (2.0 / n_features) * _mean_cos(x, w, b)
        out = cls(w=w, b=b, z=z, norm=norm, n_fitted=n, seed=seed)
        out.n_source = n
        return out

    def eval_batch(self, points) -> jax.Array:
        """Batched densities f^(points), (m,) — O(m * D), independent of the
        fitted sample size.  Routed through the Pallas tile kernel
        (`kernels/rff_eval.py`; interpret mode off-TPU)."""
        from repro.kernels import ops as kops

        p = jnp.asarray(points, jnp.float32)
        if p.ndim == 1:
            p = p[:, None]
        raw = kops.rff_density(p, self.w, self.b, self.z)
        return jnp.float32(self.norm) * raw

    def block_densities(self, points, n_blocks: int = 8) -> jax.Array:
        """(n_blocks, m) per-feature-block density replicates (see module
        docstring) — the CI pass's input."""
        p = jnp.asarray(points, jnp.float32)
        if p.ndim == 1:
            p = p[:, None]
        return _block_densities(p, self.w, self.b, self.z,
                                jnp.float32(self.norm), n_blocks)

    @property
    def nbytes(self) -> int:
        return sum(int(np.asarray(v).nbytes)
                   for v in (self.w, self.b, self.z))

    def error_metadata(self) -> Dict[str, object]:
        return {"backend": "rff", "degraded": bool(self.degraded),
                "n_features": self.n_features,
                "probe_rel_err": float(self.probe_rel_err)}

    # -- checkpointing -------------------------------------------------------

    def to_state(self) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
        arrays = {"w": np.asarray(self.w), "b": np.asarray(self.b),
                  "z": np.asarray(self.z)}
        meta = {"backend": "rff", "norm": float(self.norm),
                "n_fitted": int(self.n_fitted), "seed": int(self.seed),
                "degraded": bool(self.degraded),
                "probe_rel_err": float(self.probe_rel_err)}
        return arrays, meta

    @classmethod
    def from_state(cls, arrays: Dict[str, np.ndarray],
                   meta: Dict[str, object]) -> "RFFSynopsis":
        out = cls(w=jnp.asarray(arrays["w"]), b=jnp.asarray(arrays["b"]),
                  z=jnp.asarray(arrays["z"]), norm=float(meta["norm"]),
                  n_fitted=int(meta["n_fitted"]), seed=int(meta["seed"]))
        out.degraded = bool(meta.get("degraded", False))
        out.probe_rel_err = float(meta.get("probe_rel_err", float("nan")))
        return out
