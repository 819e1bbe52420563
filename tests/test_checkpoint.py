"""Checkpointing: atomic snapshots, keep-k, auto-resume, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import (
    latest_step,
    list_steps,
    restore_checkpoint,
    save_checkpoint,
)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)), "b": jnp.zeros((4,))},
        "opt": {"m": {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}, "step": jnp.int32(7)},
    }


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = _tree()
    save_checkpoint(d, 3, tree)
    restored, step = restore_checkpoint(d, jax.tree.map(lambda x: x, tree))
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_k_gc(tmp_path):
    d = str(tmp_path / "ckpt")
    for s in range(6):
        save_checkpoint(d, s, _tree(s), keep=3)
    assert list_steps(d) == [3, 4, 5]
    assert latest_step(d) == 5


def test_latest_pointer_crash_fallback(tmp_path):
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, _tree())
    save_checkpoint(d, 2, _tree())
    # simulate a crash that corrupted LATEST
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("garbage")
    assert latest_step(d) == 2


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "none"), _tree())


def test_elastic_restore_different_sharding(tmp_path):
    """A checkpoint saved under one device layout restores under another:
    leaves are logical arrays; shardings are applied at restore time."""
    d = str(tmp_path / "ckpt")
    tree = _tree()
    save_checkpoint(d, 9, tree)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    restored, step = restore_checkpoint(d, tree, shardings=sh)
    assert step == 9
    for leaf in jax.tree.leaves(restored):
        assert isinstance(leaf.sharding, NamedSharding)


def test_resume_training_state(tmp_path):
    """checkpoint/restart: resume from the latest snapshot and continue."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.data.synthetic import token_batches
    from repro.train import optim as O
    from repro.train.loop import init_state, make_train_step

    cfg = dataclasses.replace(
        reduced_config(get_config("granite-3-2b")),
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
        d_ff=64, vocab_size=64, remat=False,
    )
    opt = O.OptConfig(lr=1e-3, total_steps=10)
    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(cfg, opt))
    toks, labels = next(token_batches(cfg.vocab_size, 2, 8))
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    state, _ = step_fn(state, batch)
    d = str(tmp_path / "ck")
    save_checkpoint(d, 1, state)
    restored, step = restore_checkpoint(d, jax.eval_shape(lambda: state))
    assert step == 1
    state2, m = step_fn(restored, batch)
    assert np.isfinite(float(m["loss"]))
