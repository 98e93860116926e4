"""PLUGIN per axis (paper section 4.4, eqs. 12-19) and the Gaussian product
kernel (eq. 11): the reference for a configuration whose `engine.selector`
is `plugin`.

  * the bandwidth of every axis is PLUGIN, computed from that axis of the
    sample with the O(n^2) pair sums on the device in blocks, each block's
    partial sum carried to the host and added in float64;
  * a COUNT / SUM / AVG answer is the Gaussian product-kernel integral over
    the box, summed over the sample points in float64 and scaled by rows
    seen / rows kept; AVG is 0 where the count is at most 1e-3; its 95%
    interval is the normal-theory interval of the per-point terms (delta
    method for AVG);
  * `h_gap` is the largest relative gap of one axis's bandwidth.

Interface: `bench/estimators/__init__.py`.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import numpy as np
from scipy.special import erf

from bench.reference import AVG_MIN_COUNT, F64, Z95, Precision

SQRT2 = math.sqrt(2.0)
INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
K4_0 = 3.0 * INV_SQRT_2PI
K6_0 = -15.0 * INV_SQRT_2PI
R_K = 1.0 / (2.0 * math.sqrt(math.pi))


def program_bandwidth(syn) -> np.ndarray:
    """The synopsis's bandwidth per axis: its `h` (a scalar on a 1-D
    synopsis, one per axis on a joint) on every axis of its sample `x`."""
    d = 1 if syn.x.ndim == 1 else int(syn.x.shape[1])
    return np.broadcast_to(np.asarray(syn.h, np.float64), (d,)).copy()


def bandwidth(x: np.ndarray, prec: Precision = F64) -> np.ndarray:
    """PLUGIN of every axis of a (m,) or (m, d) sample."""
    x2 = x.reshape(x.shape[0], -1)
    return np.asarray([plugin_h(x2[:, j], prec) for j in range(x2.shape[1])])


def bandwidth_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative gap over the axes."""
    return float(np.max(np.abs(np.asarray(prog, np.float64) - ref) / ref))


_PAIR_FNS = {}


def _pair_sums_fn(order: int, dtype_name: str, block: int):
    """Jitted sum over row blocks of K^(order)((x_i - x_j) / g), all i, j."""
    key = (order, dtype_name, block)
    if key in _PAIR_FNS:
        return _PAIR_FNS[key]
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    def kern(t):
        t2 = t * t
        if order == 4:
            poly = (t2 - 6.0) * t2 + 3.0
        else:
            poly = ((t2 - 15.0) * t2 + 45.0) * t2 - 15.0
        return poly * jnp.exp(-0.5 * t2)

    @jax.jit
    def fn(x, w, g):
        xs = x.astype(dt)
        ws = w.astype(jnp.float32)
        gi = (1.0 / g).astype(dt)

        def one(args):
            rows, wr = args
            t = (rows[:, None] - xs[None, :]) * gi
            k = kern(t).astype(jnp.float32)
            return jnp.sum(wr[:, None] * ws[None, :] * k)

        return jax.lax.map(one, (xs.reshape(-1, block),
                                 ws.reshape(-1, block)))

    _PAIR_FNS[key] = fn
    return fn


def _pair_sum(x: np.ndarray, g: float, order: int, prec: Precision,
              block: int = 1024) -> float:
    """sum_{i,j} K^(order)((x_i - x_j) / g) / sqrt(2 pi), float64 total."""
    n = x.shape[0]
    block = min(block, 1 << max(0, (n - 1).bit_length()))
    pad = (-n) % block
    xp = np.concatenate([x, np.zeros(pad, np.float32)]).astype(np.float32)
    w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    dt = "bfloat16" if prec.name == "bfloat16" else "float32"
    parts = _pair_sums_fn(order, dt, block)(xp, w, np.float32(g))
    return float(np.sum(np.asarray(parts, np.float64))) * INV_SQRT_2PI


def plugin_h(x: np.ndarray, prec: Precision = F64) -> float:
    """PLUGIN bandwidth of a 1-D sample (paper section 4.4, eqs. 12-19)."""
    x = prec.r(np.asarray(x, np.float32)).astype(np.float32)
    n = x.shape[0]
    xd = x.astype(np.float64)
    var = float(np.sum(xd * xd) / (n - 1.0)
                - np.sum(xd) ** 2 / (n * (n - 1.0)))
    sigma = math.sqrt(var)
    psi8 = 105.0 / (32.0 * math.sqrt(math.pi) * sigma ** 9)
    g1 = (-2.0 * K6_0 / (psi8 * n)) ** (1.0 / 9.0)
    psi6 = _pair_sum(x, g1, 6, prec) / (float(n) * n * g1 ** 7)
    g2 = (-2.0 * K4_0 / (psi6 * n)) ** (1.0 / 7.0)
    psi4 = _pair_sum(x, g2, 4, prec) / (float(n) * n * g2 ** 5)
    return (R_K / (psi4 * n)) ** 0.2


def answers(boxes: Sequence[Tuple[np.ndarray, np.ndarray, int]],
            aggs: Sequence[str], x: np.ndarray, h: np.ndarray,
            n_seen: int, prec: Precision = F64, chunk: int = 16):
    """(estimate, CI half-width, reference count, target scale M) per box:
    the Gaussian product-kernel integrals over sample `x` (m, d)."""
    r = prec.r
    x = r(np.asarray(x, np.float32).reshape(x.shape[0], -1))
    m, d = x.shape
    h = r(np.asarray(h, np.float64).reshape(d))
    scale = n_seen / m
    out = []
    for s in range(0, len(boxes), chunk):
        part = boxes[s:s + chunk]
        q = len(part)
        lo = np.stack([b[0] for b in part])          # (q, d)
        hi = np.stack([b[1] for b in part])
        tgt = np.asarray([b[2] for b in part])
        za = r((r(lo)[:, None, :] - x[None]) / h)       # (q, m, d)
        zb = r((r(hi)[:, None, :] - x[None]) / h)
        d_phi_cdf = r(r(0.5 * (1.0 + erf(zb / SQRT2)))
                      - r(0.5 * (1.0 + erf(za / SQRT2))))
        d_pdf = r(r(INV_SQRT_2PI * np.exp(-0.5 * zb * zb))
                  - r(INV_SQRT_2PI * np.exp(-0.5 * za * za)))
        moment = r(r(x[None] * d_phi_cdf) - r(h * d_pdf))
        c = d_phi_cdf[..., 0]
        for j in range(1, d):
            c = r(c * d_phi_cdf[..., j])
        onehot = np.arange(d)[None, :] == tgt[:, None]      # (q, d)
        factors = np.where(onehot[:, None, :], moment, d_phi_cdf)
        sv = factors[..., 0]
        for j in range(1, d):
            sv = r(sv * factors[..., j])
        acc = prec.acc
        s1c = np.sum(c.astype(acc), axis=1, dtype=acc).astype(np.float64)
        s1s = np.sum(sv.astype(acc), axis=1, dtype=acc).astype(np.float64)
        s2c = np.sum((c * c).astype(acc), axis=1, dtype=acc).astype(np.float64)
        s2s = np.sum((sv * sv).astype(acc), axis=1,
                     dtype=acc).astype(np.float64)
        s12 = np.sum((c * sv).astype(acc), axis=1,
                     dtype=acc).astype(np.float64)
        corr = m / (m - 1.0)
        count = scale * s1c
        total = scale * s1s
        for i in range(q):
            agg = aggs[s + i]
            mt = float(np.max(np.abs(x[:, tgt[i]]))) + float(h[tgt[i]])
            if agg == "count":
                est = count[i]
                se = scale * math.sqrt(corr * max(s2c[i] - s1c[i] ** 2 / m,
                                                  0.0))
            elif agg == "sum":
                est = total[i]
                se = scale * math.sqrt(corr * max(s2s[i] - s1s[i] ** 2 / m,
                                                  0.0))
            else:
                ok = count[i] > AVG_MIN_COUNT
                ratio = s1s[i] / s1c[i] if ok else 0.0
                est = total[i] / count[i] if ok else 0.0
                quad = max(s2s[i] - 2.0 * ratio * s12[i]
                           + ratio * ratio * s2c[i], 0.0)
                se = (scale * math.sqrt(corr * quad)
                      / max(count[i], AVG_MIN_COUNT)) if ok else math.inf
            out.append((float(est), Z95 * float(se), float(count[i]), mt))
    return out
