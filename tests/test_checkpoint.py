"""Checkpointing: roundtrip, atomicity, keep-k, async, reshard-restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"a": {"w": jax.random.normal(k, (8, 16), jnp.float32)},
            "b": jnp.arange(10, dtype=jnp.int32),
            "c": jax.random.normal(k, (4,), jnp.bfloat16)}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = _tree()
    mgr.save(5, tree, {"step": 5, "pipeline": {"step": 5, "seed": 0}})
    assert mgr.latest_step() == 5
    restored, extra = mgr.restore(5, jax.eval_shape(lambda: tree))
    assert extra["step"] == 5
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in [1, 2, 3, 4]:
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(7, _tree())
    mgr.wait()
    assert mgr.latest_step() == 7


def test_atomicity_tmp_dirs_invisible(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    os.makedirs(os.path.join(str(tmp_path), "tmp.99"), exist_ok=True)  # crashed write
    mgr.save(1, _tree())
    assert mgr.all_steps() == [1]           # tmp.* never surfaces


def test_reshard_restore(tmp_path):
    """Save unsharded, restore with explicit shardings (elastic-rescale path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    mgr.save(1, tree)
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = mgr.restore(1, jax.eval_shape(lambda: tree), shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
