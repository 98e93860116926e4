"""Public wrappers for the Pallas kernels.

Off the TPU (tests run with JAX_PLATFORMS=cpu) kernels run in interpret
mode — the kernel body executes in Python on the CPU, the correctness path;
on a TPU they compile through Mosaic.  `tuning.interpret()` is the one place
that decides, per call, for these wrappers and the autotuner alike.

Tile sizes resolve at CALL time, never at import: explicit kwarg > measured
tile cache (`kernels/autotune.py`, keyed by kernel + bucketed shape) > env
var > module default.  Wrappers that sit inside a jitted caller (the 1-D and
box batch paths) resolve once per traced shape — tile choices are static
under jit anyway, so per-shape trace-time resolution is exactly as fresh as
a recompile.

With `repro.obs` enabled, every wrapper routes through
`tuning.profiled_call`, which records fenced wall/dispatch timings into the
process-global metrics registry keyed by (kernel, shape, tile, interpret) —
so a run can show which kernels compiled and which were interpreted.
Disabled (the default), and in `repro.obs` profiler mode, each wrapper
takes the direct branch — same jitted callable, no fencing, no extra work.
"""
from __future__ import annotations

from repro import obs

from . import aqp_batch as _ab
from . import aqp_boxes as _abx
from . import aqp_grouped as _agr
from . import autotune as _tune
from . import gh_fused as _gh
from . import kde_eval as _kde
from . import lscv_grid as _lg
from . import pairwise_reduce as _pr
from . import qmc_reduce as _qmc
from . import rff_eval as _rff
from .tuning import interpret, profiled_call


def _dispatch(kernel: str, run, **labels):
    """Call `run(interpret)` directly, or profiled (fenced) when
    `repro.obs.enabled()`."""
    interp = interpret()
    if not obs.enabled():
        return run(interp)
    return profiled_call(kernel, lambda: run(interp), interpret=interp,
                         **labels)


def pairwise_scaled_ksum(x, g, kind="k4", tile=None):
    (tile,) = _tune.resolve(
        "pairwise_scaled_ksum", {"n": x.shape[0]},
        tile=(tile, "REPRO_PAIRWISE_TILE", _pr.TILE))
    return _dispatch(
        "pairwise_scaled_ksum",
        lambda interp: _pr.pairwise_scaled_ksum(x, g, kind=kind, tile=tile,
                                                interpret=interp),
        n=x.shape[0], kind=kind, tile=tile)


def gh_fused_sum(x, h_inv, c_k, c_kk, tile=None):
    d = x.shape[1] if x.ndim > 1 else 1
    (tile,) = _tune.resolve(
        "gh_fused_sum", {"n": x.shape[0], "d": d},
        tile=(tile, "REPRO_GH_TILE", _gh.TILE))
    return _dispatch(
        "gh_fused_sum",
        lambda interp: _gh.gh_fused_sum(x, h_inv, c_k, c_kk, tile=tile,
                                        interpret=interp),
        n=x.shape[0], d=d, tile=tile)


def lscv_grid_sums(x, sigma_inv, scales, c_k, c_kk, tile=None, weights=None,
                   tied=None):
    (tile,) = _tune.resolve(
        "lscv_grid_sums", {"n": x.shape[0], "G": scales.shape[0]},
        tile=(tile, "REPRO_LSCV_TILE", _lg.TILE))
    return _dispatch(
        "lscv_grid_sums",
        lambda interp: _lg.lscv_grid_sums(x, sigma_inv, scales, c_k, c_kk,
                                          tile=tile, interpret=interp,
                                          weights=weights, tied=tied),
        n=x.shape[0], G=scales.shape[0], tile=tile)


def kde_eval(points, x, h, tile=None):
    (tile,) = _tune.resolve(
        "kde_eval", {"n": x.shape[0], "G": points.shape[0]},
        tile=(tile, "REPRO_KDE_EVAL_TILE", _kde.TILE))
    return _dispatch(
        "kde_eval",
        lambda interp: _kde.kde_eval(points, x, h, tile=tile,
                                     interpret=interp),
        n=x.shape[0], G=points.shape[0], tile=tile)


def aqp_batch_sums(x, h, a, b, tile=None, q_tile=None):
    tile, q_tile = _tune.resolve(
        "aqp_batch_sums", {"n": x.shape[0], "G": a.shape[0]},
        tile=(tile, "REPRO_AQP_TILE", _ab.TILE),
        q_tile=(q_tile, "REPRO_AQP_Q_TILE", _ab.Q_TILE))
    return _dispatch(
        "aqp_batch_sums",
        lambda interp: _ab.aqp_batch_sums(x, h, a, b, tile=tile,
                                          q_tile=q_tile, interpret=interp),
        n=x.shape[0], G=a.shape[0], tile=tile, q_tile=q_tile)


def rff_density(points, w, b, z, tile=None, p_tile=None):
    shape = {"n": w.shape[0], "d": points.shape[1], "G": points.shape[0]}
    tile, p_tile = _tune.resolve(
        "rff_density", shape,
        tile=(tile, "REPRO_RFF_TILE", _rff.TILE),
        p_tile=(p_tile, "REPRO_RFF_P_TILE", _rff.P_TILE))
    return _dispatch(
        "rff_density",
        lambda interp: _rff.rff_density(points, w, b, z, tile=tile,
                                        p_tile=p_tile, interpret=interp),
        n=points.shape[0], D=w.shape[0], tile=tile, p_tile=p_tile)


def aqp_box_sums(x, h_diag, lo, hi, tgt, tile=None, q_tile=None):
    d = x.shape[1] if x.ndim > 1 else 1
    tile, q_tile = _tune.resolve(
        "aqp_box_sums", {"n": x.shape[0], "d": d, "G": lo.shape[0]},
        tile=(tile, "REPRO_AQP_BOXES_TILE", _abx.TILE),
        q_tile=(q_tile, "REPRO_AQP_BOXES_Q_TILE", _abx.Q_TILE))
    return _dispatch(
        "aqp_box_sums",
        lambda interp: _abx.aqp_box_sums(x, h_diag, lo, hi, tgt, tile=tile,
                                         q_tile=q_tile, interpret=interp),
        n=x.shape[0], d=d, G=lo.shape[0], tile=tile, q_tile=q_tile)


def aqp_grouped_sums(x, h_diag, lo, hi, glo, ghi, g_axis, tgt,
                     tile=None, g_tile=None):
    shape = {"n": x.shape[0], "d": x.shape[1], "G": glo.shape[0]}
    tile, g_tile = _tune.resolve(
        "aqp_grouped_sums", shape,
        tile=(tile, "REPRO_AQP_GROUPED_TILE", _agr.TILE),
        g_tile=(g_tile, "REPRO_AQP_GROUPED_G_TILE", _agr.G_TILE))
    return _dispatch(
        "aqp_grouped_sums",
        lambda interp: _agr.aqp_grouped_sums(
            x, h_diag, lo, hi, glo, ghi, g_axis, tgt, tile=tile,
            g_tile=g_tile, interpret=interp),
        n=x.shape[0], d=x.shape[1], G=glo.shape[0], tile=tile, g_tile=g_tile)


def qmc_box_reduce(nodes, x, h_inv, log_norm, lo, hi, tgt,
                   tile=None, m_tile=None, q_tile=None):
    shape = {"n": x.shape[0], "d": x.shape[1], "G": lo.shape[0],
             "m": nodes.shape[0]}
    tile, m_tile, q_tile = _tune.resolve(
        "qmc_box_reduce", shape,
        tile=(tile, "REPRO_QMC_TILE", _qmc.TILE),
        m_tile=(m_tile, "REPRO_QMC_M_TILE", _qmc.M_TILE),
        q_tile=(q_tile, "REPRO_QMC_Q_TILE", _qmc.Q_TILE))
    return _dispatch(
        "qmc_box_reduce",
        lambda interp: _qmc.qmc_box_reduce(
            nodes, x, h_inv, log_norm, lo, hi, tgt, tile=tile, m_tile=m_tile,
            q_tile=q_tile, interpret=interp),
        n=x.shape[0], d=x.shape[1], G=lo.shape[0], m=nodes.shape[0],
        tile=tile, m_tile=m_tile, q_tile=q_tile)
