"""Optimizer / checkpoint / straggler / compression infrastructure tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt
from repro.train import straggler
from repro.distributed import compression


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_adamw_minimizes_quadratic(key):
    target = jax.random.normal(key, (32,))
    params = {"w": jnp.zeros((32,))}
    cfg = opt.OptConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    state = opt.init_state(params, None, cfg)
    for _ in range(200):
        grads = {"w": state.params["w"] - target}
        state, m = opt.apply_updates(state, grads, cfg)
    np.testing.assert_allclose(np.asarray(state.params["w"]),
                               np.asarray(target), atol=0.05)


def test_grad_clip():
    grads = {"a": jnp.full((10,), 100.0)}
    clipped, gn = opt.clip_by_global_norm(grads, 1.0)
    assert float(gn) > 100
    total = float(jnp.linalg.norm(clipped["a"]))
    assert total == pytest.approx(1.0, rel=1e-4)


def test_master_weights_fp32(key):
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    cfg = opt.OptConfig()
    state = opt.init_state(params, None, cfg)
    assert state.master["w"].dtype == jnp.float32
    state, _ = opt.apply_updates(state, {"w": jnp.ones((4,), jnp.bfloat16)},
                                 cfg)
    assert state.params["w"].dtype == jnp.bfloat16
    assert state.master["w"].dtype == jnp.float32


def test_zero_pspec_folds_dp_axes():
    from jax.sharding import PartitionSpec as P

    from jax.sharding import AbstractMesh
    # abstract mesh: zero_pspec only reads axis sizes
    mesh = AbstractMesh((4, 2), ("data", "model"))
    spec = opt.zero_pspec(P(None, "model"), (64, 32), mesh, ("data",))
    assert spec == P("data", "model")
    # non-divisible first dim falls through to the next dim
    spec2 = opt.zero_pspec(P(None, None), (7, 64), mesh, ("data",))
    assert spec2 == P(None, "data")
    # nothing divisible → unchanged
    spec3 = opt.zero_pspec(P(None,), (7,), mesh, ("data",))
    assert spec3 == P(None,)


def test_warmup_schedule():
    cfg = opt.OptConfig(lr=1.0, warmup_steps=10)
    assert float(opt.lr_at(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(opt.lr_at(cfg, jnp.asarray(100))) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Checkpointing / fault tolerance
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, key):
    tree = {"params": {"w": jax.random.normal(key, (8, 4))},
            "step": jnp.asarray(7, jnp.int32),
            "reservoir": jax.random.normal(key, (3, 16))}
    ckpt.save(str(tmp_path), 7, tree)
    assert ckpt.latest_step(str(tmp_path)) == 7
    restored = ckpt.restore(str(tmp_path), 7, tree)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_checkpoint_atomicity_and_gc(tmp_path, key):
    tree = {"w": jnp.ones((4,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree, keep_last=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    steps = sorted(os.listdir(str(tmp_path)))
    assert len([s for s in steps if s.startswith("step_")]) == 2
    # a dir without COMMIT is ignored
    os.makedirs(str(tmp_path / "step_00000099"))
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_async_checkpointer(tmp_path, key):
    tree = {"w": jax.random.normal(key, (128, 128))}
    ac = ckpt.AsyncCheckpointer(str(tmp_path))
    ac.save(1, tree)
    ac.save(2, jax.tree.map(lambda x: x + 1, tree))   # waits for save 1
    ac.wait()
    assert ckpt.latest_step(str(tmp_path)) == 2
    restored = ckpt.restore(str(tmp_path), 2, tree)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]) + 1)


def test_restore_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError, match="shape"):
        ckpt.restore(str(tmp_path), 1, {"w": jnp.ones((5,))})


# ---------------------------------------------------------------------------
# Straggler mitigation
# ---------------------------------------------------------------------------

def test_straggler_reweight():
    w = jnp.ones((8,))
    alive = jnp.array([1.0, 1.0, 0.0, 1.0])     # worker 2 dead
    shard_of = jnp.array([0, 0, 1, 1, 2, 2, 3, 3])
    out = straggler.reweight_for_stragglers(w, alive, shard_of)
    np.testing.assert_allclose(np.asarray(out[4:6]), 0.0)
    np.testing.assert_allclose(np.asarray(out[0]), 4 / 3, rtol=1e-5)
    # total weight preserved in expectation: 6 × 4/3 = 8
    np.testing.assert_allclose(float(jnp.sum(out)), 8.0, rtol=1e-5)


def test_window_deadline():
    d = straggler.WindowDeadline(num_shards=3, deadline_sec=100.0)
    d.start_window()
    d.mark_arrival(0)
    d.mark_arrival(2)
    np.testing.assert_array_equal(np.asarray(d.alive_mask()), [1, 0, 1])
    assert not d.expired()


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def _run_sharded(fn, *args):
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("pod",))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(P() for _ in args),
                         out_specs=P())(*args)


def test_psum_int8_accuracy(key):
    g = jax.random.normal(key, (512,)) * 0.01
    out = _run_sharded(lambda x: compression.psum_int8(x, "pod"), g)
    err = float(jnp.max(jnp.abs(out - g))) / float(jnp.max(jnp.abs(g)))
    assert err < 0.01      # ≤ 1/127 quantization error


def test_psum_bf16_accuracy(key):
    g = jax.random.normal(key, (512,))
    out = _run_sharded(lambda x: compression.psum_bf16(x, "pod"), g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=1e-2)


def test_hierarchical_sync_single_device(key):
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    g = jax.random.normal(key, (64,))
    fn = jax.shard_map(
        lambda x: compression.hierarchical_grad_sync(x, "data", "pod",
                                                     "int8"),
        mesh=mesh, in_specs=P(), out_specs=P())
    out = fn(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.02)
