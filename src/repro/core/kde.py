"""Kernel density estimation (paper §4.2, eqs. 3-8).

`kde_eval`   — scalar-h estimator f^(x, h) (eq. 3), any d.
`kde_eval_H` — full-matrix estimator f^(x, H) (eq. 6).

Both are the O(m*n) "direct evaluation" the paper discusses in §2.2; the
binned/FFT accelerations from the related-work section live in binned.py.
The evaluation loop is chunked over evaluation points so memory stays
O(chunk * n); the TPU hot-spot kernel is kernels/kde_eval.py.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def _chunked_eval(points: jax.Array, x: jax.Array, kfun, chunk: int):
    """mean over data of kfun(p - x), scanned over eval chunks."""
    m = points.shape[0]
    c = min(chunk, m)
    pad = (-m) % c
    pp = jnp.pad(points, ((0, pad), (0, 0)))

    def body(_, p_chunk):
        diff = p_chunk[:, None, :] - x[None, :, :]         # (c, n, d)
        return None, jnp.mean(kfun(diff), axis=1)

    _, vals = jax.lax.scan(body, None, pp.reshape(-1, c, points.shape[1]))
    return vals.reshape(-1)[:m]


# --- product-kernel profiles (paper §4.2: "Other commonly used kernel
# functions are Epanechnikov, uniform, triangular, biweight") -------------
# Each maps |u| <= ... per-dimension; constants normalise to integrate to 1.

def _profiles(kind: str, d: int, h):
    if kind == "gaussian":
        ln = -d / 2.0 * math.log(2.0 * math.pi) - d * jnp.log(h)
        return lambda diff: jnp.exp(ln - 0.5 * jnp.sum((diff / h) ** 2, axis=-1))
    per_dim = {
        "epanechnikov": (0.75, lambda u: jnp.maximum(1.0 - u * u, 0.0)),
        "biweight": (15.0 / 16.0, lambda u: jnp.maximum(1.0 - u * u, 0.0) ** 2),
        "triangular": (1.0, lambda u: jnp.maximum(1.0 - jnp.abs(u), 0.0)),
        "uniform": (0.5, lambda u: (jnp.abs(u) <= 1.0).astype(jnp.float32)),
    }[kind]
    cst, prof = per_dim

    def kfun(diff):
        u = diff / h
        return jnp.prod(cst * prof(u), axis=-1) / h ** d

    return kfun


@partial(jax.jit, static_argnames=("chunk", "backend", "kind"))
def kde_eval(points: jax.Array, x: jax.Array, h: jax.Array, chunk: int = 256,
             backend: str = "jnp", kind: str = "gaussian") -> jax.Array:
    """f^(points; x, h) per eq. (3).  kind selects the kernel function —
    Gaussian (eq. 5, default) or the compact-support kernels the paper lists
    in §4.2 (Epanechnikov / biweight / triangular / uniform, product form).

    points: (m, d) or (m,); x: (n, d) or (n,); returns (m,).
    """
    if x.ndim == 1:
        x = x[:, None]
    if points.ndim == 1:
        points = points[:, None]
    n, d = x.shape

    if backend == "pallas":
        from repro.kernels import ops as kops
        assert kind == "gaussian", "pallas kde kernel implements the Gaussian"
        return kops.kde_eval(points, x, h)

    return _chunked_eval(points, x, _profiles(kind, d, h), chunk)


@partial(jax.jit, static_argnames=("chunk",))
def _kde_eval_H(points: jax.Array, x: jax.Array, H: jax.Array, chunk: int) -> jax.Array:
    if x.ndim == 1:
        x = x[:, None]
    if points.ndim == 1:
        points = points[:, None]
    n, d = x.shape
    H_inv = jnp.linalg.inv(H)
    _, logdet = jnp.linalg.slogdet(H)
    log_norm = -d / 2.0 * math.log(2.0 * math.pi) - 0.5 * logdet

    def kfun(diff):
        quad = 0.5 * jnp.einsum("cnd,de,cne->cn", diff, H_inv, diff,
                                precision=jax.lax.Precision.HIGHEST)
        return jnp.exp(log_norm - quad)

    return _chunked_eval(points, x, kfun, chunk)


def kde_eval_H(points: jax.Array, x: jax.Array, H: jax.Array,
               chunk: int | None = None) -> jax.Array:
    """f^(points; x, H) per eq. (6): n^-1 |H|^-1/2 sum K(H^-1/2 (x - X_i)).

    chunk=None reads REPRO_KDE_CHUNK (default 256) per call — the env var
    must be resolved outside the jit, so this wrapper stays un-jitted and
    delegates to the jitted body (jit-of-jit inlines, so callers that trace
    this inside their own jit compile identically).
    """
    if chunk is None:
        from repro.kernels.tuning import env_int
        chunk = env_int("REPRO_KDE_CHUNK", 256)
    return _kde_eval_H(points, x, H, chunk)


def silverman_h(x: jax.Array) -> jax.Array:
    """Rule-of-thumb bandwidth (paper §2.3 'first class' selector), 1-D."""
    n = x.shape[0]
    std = jnp.std(x, ddof=1)
    iqr = jnp.percentile(x, 75) - jnp.percentile(x, 25)
    a = jnp.minimum(std, iqr / 1.349)
    return 0.9 * a * n ** (-0.2)
