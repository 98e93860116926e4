"""Silverman's rule of thumb per axis (paper section 2.3) with the Gaussian
product kernel: the reference for a configuration whose `engine.selector`
is `silverman`, kept with the tests to show that the check follows the
selector.  Per axis, h = 0.9 min(sigma, IQR / 1.349) n^(-1/5), with sigma
the sample standard deviation (n - 1) and the quartiles linearly
interpolated; computed in float64.  On a flag column whose middle half is
one value the rule gives h = 0, as the program's does: that axis's kernel
is then a point mass, and both sides count its rows exactly.  The answers
and the gap are PLUGIN's module's: the kernel is the same, only the
bandwidth differs.
"""
import numpy as np

from bench import harness
from bench.reference import F64, Precision

_plugin = harness.load_module("estimators", "plugin")
program_bandwidth = _plugin.program_bandwidth
bandwidth_gap = _plugin.bandwidth_gap
answers = _plugin.answers


def silverman_h(x: np.ndarray, prec: Precision = F64) -> float:
    x = np.asarray(prec.r(np.asarray(x, np.float32)), np.float64)
    q25, q75 = np.percentile(x, [25.0, 75.0])
    a = min(float(np.std(x, ddof=1)), float(q75 - q25) / 1.349)
    return 0.9 * a * x.shape[0] ** -0.2


def bandwidth(x: np.ndarray, prec: Precision = F64) -> np.ndarray:
    x2 = x.reshape(x.shape[0], -1)
    return np.asarray([silverman_h(x2[:, j], prec)
                       for j in range(x2.shape[1])])
