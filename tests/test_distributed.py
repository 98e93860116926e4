"""Distributed (shard_map) KDE selectors, on a multi-device placeholder
mesh via subprocess (tests keep 1 device locally)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gaussian as G
from repro.core.distributed import distributed_lscv_h, sharded_pairwise_reduce
from repro.core.reductions import pairwise_reduce


def _mesh1():
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def test_sharded_pairwise_single_device(rng):
    mesh = _mesh1()
    x = jnp.asarray(rng.normal(0, 1, 500).astype(np.float32))
    fun = lambda d: G.k6(d / 0.4)
    a = float(sharded_pairwise_reduce(fun, x, mesh))
    b = float(pairwise_reduce(fun, x))
    assert a == pytest.approx(b, rel=1e-4)


def test_distributed_lscv_h_single_device(rng):
    from repro.core import lscv_h
    mesh = _mesh1()
    x = jnp.asarray(rng.normal(0, 1, (200, 2)).astype(np.float32))
    h, grid, g = distributed_lscv_h(x, mesh, n_h=15)
    ref = lscv_h(x, n_h=15)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref.g_values), rtol=1e-3)
    assert float(h) == pytest.approx(float(ref.h), rel=1e-4)


def test_multi_device_agreement_subprocess():
    """8 placeholder devices: distributed == single-path results."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import distributed_lscv_h, sharded_pairwise_reduce
from repro.core.reductions import pairwise_reduce
from repro import compat
from repro.core import gaussian as G, lscv_h
rng = np.random.default_rng(1)
mesh = jax.make_mesh((4, 2), ("data", "model"))
x = jnp.asarray(rng.normal(0, 1, 1000).astype(np.float32))
fun = lambda d: G.k4(d / 0.3)
a = float(sharded_pairwise_reduce(fun, x, mesh))
b = float(pairwise_reduce(fun, x))
assert abs(a - b) / abs(b) < 1e-3, (a, b)
x2 = jnp.asarray(rng.normal(0, 1, (300, 3)).astype(np.float32))
h, grid, g = distributed_lscv_h(x2, mesh, n_h=20)
ref = lscv_h(x2, n_h=20)
np.testing.assert_allclose(np.asarray(g), np.asarray(ref.g_values), rtol=2e-3)
print("MULTIDEV_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo", timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MULTIDEV_OK" in r.stdout
