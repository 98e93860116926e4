"""All ten Pallas kernels compile for a TPU v5e, without a chip.

Each kernel is lowered with `interpret=False` for one chip of a described
`v5e:2x2` topology at the shapes the serving path produces (32,768-row
reservoirs, d=2, batches padded pow2-then-x64 below and above one query
tile, 4,096 QMC nodes, 2,048 RFF features, the 150-point LSCV_h grid) and
compiled by the installed TPU compiler; the compiled program must contain
the Mosaic kernel (`tpu_custom_call`).  Interpret-mode tests cannot see
what this catches: unaligned blocks, primitives without a Mosaic lowering,
layouts XLA and Mosaic disagree on.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU library, and every worker
collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (aqp_batch, aqp_boxes, aqp_grouped, gh_fused,
                           kde_eval, lscv_grid, pairwise_reduce, qmc_reduce,
                           rff_eval, sv_precompute)

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
        ("sv_precompute",
         lambda x, m: sv_precompute.sv_matrix(x, m, interpret=False),
         [((SUBSAMPLE, D), F32), ((D, D), F32)]),
        ("gh_fused",
         lambda x, m, ck, ckk: gh_fused.gh_fused_sum(x, m, ck, ckk,
                                                     interpret=False),
         [((N, D), F32), ((D, D), F32), ((), F32), ((), F32)]),
        ("lscv_grid",
         lambda x, si, hg, ck, ckk: lscv_grid.lscv_grid_sums(
             x, si, hg, ck, ckk, interpret=False),
         [((SUBSAMPLE, D), F32), ((D, D), F32), ((N_H,), F32), ((), F32),
          ((), F32)]),
    ]
    return cases


CASES = _cases()


@pytest.mark.parametrize("name,fn,shapes", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
