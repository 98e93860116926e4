"""Pallas TPU kernels for the paper's compute hot spots (§5).

pairwise_reduce — RR_fun triangular reduction (PLUGIN psi sums)      [§5.4]
lscv_grid       — streaming triangular LSCV_h grid sums (all h)     [§4.5/§6.2]
gh_fused        — fused quadratic-form + T_H reduction (LSCV_H)      [§6.3]
kde_eval        — direct KDE evaluation (AQP serving)                [eq. 3]
aqp_batch       — batched (queries x sample) Phi-diff reduction      [eqs. 9-10]
aqp_boxes       — batched (queries x samples x dims) box reduction   [eq. 11]
aqp_grouped     — factored GROUP BY box reduction                    [eq. 11]
qmc_reduce      — fused quasi-MC box reduction (full-H synopses)
rff_eval        — random-Fourier-feature density evaluation
erf             — f32 erf / Phi terms Mosaic can lower (closed forms)
triangle        — Appendix-A tile index math (eqs. 49/50)
ops             — jitted wrappers; ref — pure-jnp oracles
tuning          — tile resolution, Mosaic block rounding, interpret()
"""
from . import ops, ref, triangle
