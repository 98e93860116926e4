"""All nine Pallas kernels compile for a TPU v5e, without a chip.

Each kernel is lowered with `interpret=False` for one chip of a described
`v5e:2x2` topology at the shapes the serving path produces (32,768-row
reservoirs, d=2, batches padded pow2-then-x64 below and above one query
tile, 4,096 QMC nodes, 2,048 RFF features, the 150-point LSCV_h grid, the
served 1-D LSCV_h fit at 32,768 rows) and compiled by the installed TPU
compiler; the compiled program must contain
the Mosaic kernel (`tpu_custom_call`) under the kernel's stable name (the
`name=` of its `pallas_call`, which a profiler trace shows as the
operation's name).  Interpret-mode tests cannot see
what this catches: unaligned blocks, primitives without a Mosaic lowering,
layouts XLA and Mosaic disagree on.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU library, and every worker
collects the same tests.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (aqp_batch, aqp_boxes, aqp_grouped, gh_fused,
                           kde_eval, lscv_grid, pairwise_reduce, qmc_reduce,
                           rff_eval)

N, D = 32_768, 2          # reservoir rows, joint dimensions
M_QMC, FEATURES = 4_096, 2_048
SUBSAMPLE, N_H = 2_048, 150
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cases():
    """(id, kernel call with interpret=False, argument shapes)."""
    cases = []
    for q in (8, 192):      # one query tile, and several (pow2 then x64)
        cases += [
            (f"aqp_batch-q{q}",
             lambda x, h, a, b: aqp_batch.aqp_batch_sums(
                 x, h, a, b, interpret=False),
             [((N,), F32), ((), F32), ((q,), F32), ((q,), F32)]),
            (f"aqp_boxes-q{q}",
             lambda x, h, lo, hi, t: aqp_boxes.aqp_box_sums(
                 x, h, lo, hi, t, interpret=False),
             [((N, D), F32), ((D,), F32), ((q, D), F32), ((q, D), F32),
              ((q,), I32)]),
            (f"qmc_reduce-q{q}",
             lambda nd, x, hinv, ln, lo, hi, t: qmc_reduce.qmc_box_reduce(
                 nd, x, hinv, ln, lo, hi, t, interpret=False),
             [((M_QMC, D), F32), ((N, D), F32), ((D, D), F32), ((), F32),
              ((q, D), F32), ((q, D), F32), ((q,), I32)]),
        ]
    for tgt in (0, 1):      # target on the group axis, and on a kept axis
        cases.append(
            (f"aqp_grouped-tgt{tgt}",
             lambda x, h, lo, hi, glo, ghi, tgt=tgt:
                 aqp_grouped.aqp_grouped_sums(x, h, lo, hi, glo, ghi, 0, tgt,
                                              interpret=False),
             [((N, D), F32), ((D,), F32), ((D,), F32), ((D,), F32),
              ((8,), F32), ((8,), F32)]))
    cases += [
        ("rff_eval",
         lambda p, w, b, z: rff_eval.rff_density(p, w, b, z,
                                                 interpret=False),
         [((M_QMC, D), F32), ((FEATURES, D), F32), ((FEATURES,), F32),
          ((FEATURES,), F32)]),
        ("kde_eval",
         lambda p, x, h: kde_eval.kde_eval(p, x, h, interpret=False),
         [((M_QMC, D), F32), ((N, D), F32), ((), F32)]),
        ("pairwise_reduce",
         lambda x, g: pairwise_reduce.pairwise_scaled_ksum(
             x, g, kind="k6", interpret=False),
         [((N,), F32), ((), F32)]),
        ("gh_fused",
         lambda x, m, ck, ckk: gh_fused.gh_fused_sum(x, m, ck, ckk,
                                                     interpret=False),
         [((N, D), F32), ((D, D), F32), ((), F32), ((), F32)]),
    ]
    # the library's d > 1 fit, and the served 1-D fit at the reservoir's size
    for name, rows, d in (("lscv_grid", SUBSAMPLE, D), ("lscv_grid-d1", N, 1)):
        cases.append(
            (name,
             lambda x, si, sc, ck, ckk: lscv_grid.lscv_grid_sums(
                 x, si, sc, ck, ckk, interpret=False),
             [((rows, d), F32), ((d, d), F32), ((N_H,), F32), ((), F32),
              ((), F32)]))
    return cases


CASES = _cases()

# the operation name each kernel keeps in a profiler trace
KERNEL_NAMES = {
    "aqp_batch": "_aqp_batch_sums", "aqp_boxes": "_aqp_box_sums",
    "qmc_reduce": "_qmc_box_reduce", "aqp_grouped": "_aqp_grouped_sums",
    "rff_eval": "_rff_density", "kde_eval": "_kde_eval",
    "pairwise_reduce": "_pairwise_scaled_ksum",
    "gh_fused": "_gh_fused_sum", "lscv_grid": "_lscv_grid_sums",
}


@pytest.mark.parametrize("name,fn,shapes", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
    kernel = KERNEL_NAMES[name.split("-")[0]]
    assert re.search(rf"%{kernel}\.\d+ = .*custom_call_target=\"tpu_custom_call\"",
                     text), (name, kernel)
