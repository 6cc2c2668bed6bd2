"""Training substrate: optimizer, microbatching, compression, checkpoint,
fault-tolerance policies."""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.train import checkpoint, compression, fault
from repro.train.optimizer import AdamW
from repro.train.trainer import make_train_step

KEY = jax.random.PRNGKey(0)


def _quad_loss(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _toy():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    w_true = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
    y = x @ w_true + 0.3
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    return params, x, y


def test_adamw_converges():
    params, x, y = _toy()
    opt = AdamW(lr=5e-2)
    step = jax.jit(make_train_step(_quad_loss, opt))
    state = opt.init(params)
    losses = []
    for _ in range(200):
        params, state, m = step(params, state, x, y)
        losses.append(float(m["loss"]))
    assert losses[-1] < 1e-3 < losses[0]


def test_grad_accum_matches_full_batch():
    params, x, y = _toy()
    opt = AdamW(lr=1e-2, grad_clip=None)
    full = make_train_step(_quad_loss, opt)
    micro = make_train_step(_quad_loss, opt, grad_accum=4)
    p1, s1, m1 = jax.jit(full)(params, opt.init(params), x, y)
    xm = x.reshape(4, 16, 4)
    ym = y.reshape(4, 16)
    p2, s2, m2 = jax.jit(micro)(params, opt.init(params), xm, ym)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)


def test_compression_error_feedback_converges():
    params, x, y = _toy()
    opt = AdamW(lr=5e-2)
    step = jax.jit(make_train_step(_quad_loss, opt, compress=True))
    state = opt.init(params)
    err = None
    for _ in range(300):
        params, state, m, err = step(params, state, x, y, error_fb=err)
    assert float(m["loss"]) < 1e-2


def test_compression_bounded_error():
    g = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(128,)).astype(np.float32))}
    cg, err = compression.compress_decompress(g)
    scale = float(jnp.max(jnp.abs(g["a"]))) / 127.0
    assert float(jnp.max(jnp.abs(cg["a"] - g["a"]))) <= scale * 1.01


def test_checkpoint_roundtrip(tmp_path):
    params, x, y = _toy()
    opt = AdamW(lr=1e-2)
    state = opt.init(params)
    tree = {"params": params, "opt": state}
    path = os.path.join(tmp_path, "step_10")
    checkpoint.save(path, tree, step=10)
    like = jax.tree.map(lambda a: jnp.zeros_like(a), tree)
    restored, step = checkpoint.restore(path, like)
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_and_latest(tmp_path):
    tree = {"w": jnp.ones(3)}
    checkpoint.save(os.path.join(tmp_path, "step_1"), tree, step=1)
    checkpoint.save(os.path.join(tmp_path, "step_20"), tree, step=20)
    latest = checkpoint.latest_step_dir(str(tmp_path))
    assert latest.endswith("step_20")


def test_checkpoint_elastic_restore_across_mesh(tmp_path):
    """Write unsharded, restore onto a 1-device 'mesh' sharding (the elastic
    path device_put's through NamedSharding)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    path = os.path.join(tmp_path, "step_5")
    checkpoint.save(path, tree, step=5)
    shardings = {"w": NamedSharding(mesh, P("data"))}
    restored, _ = checkpoint.restore(path, tree, shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8))


def test_retry_policy_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("simulated preemption")
        return "ok"

    pol = fault.RetryPolicy(max_retries=3, backoff_s=0.0)
    restores = []
    assert pol.run(flaky, on_failure=lambda a, e: restores.append(a)) == "ok"
    assert calls["n"] == 3 and len(restores) == 2


def test_retry_policy_gives_up():
    pol = fault.RetryPolicy(max_retries=1, backoff_s=0.0)
    with pytest.raises(RuntimeError):
        pol.run(lambda: (_ for _ in ()).throw(RuntimeError("dead")))


def test_straggler_detector():
    det = fault.StragglerDetector(warmup_steps=2, threshold=2.0)
    flags = [det.observe(t) for t in [5.0, 5.0, 0.1, 0.1, 0.1, 0.1, 1.0]]
    assert flags[-1] is True and not any(flags[:-1])
