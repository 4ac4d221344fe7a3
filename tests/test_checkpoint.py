"""Fault tolerance: delta-compressed checkpointing, restart, elasticity,
gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.optim import adamw_init

KEY = jax.random.PRNGKey(0)


def _small_state():
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = init_params(cfg, KEY)
    opt = adamw_init(params)
    return cfg, params, opt


def test_checkpoint_roundtrip(tmp_path):
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(10, params, opt)
    step, state = mgr.restore()
    assert step == 10
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(state["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2 ** -23, rtol=0)
    assert int(state["opt"]["step"]) == int(opt["step"])


def test_checkpoint_delta_compression_across_steps(tmp_path):
    """Consecutive checkpoints delta-encode against each other: step 2+
    pages must be much smaller than step 1 (the paper's mechanism applied
    to training)."""
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path), tolerance=1e-6)
    mgr.save(0, params)
    first = mgr.engine._meta["models"]["ckpt-0"]
    # Simulate a few optimizer steps: small drift.
    for step in (1, 2):
        params = jax.tree.map(
            lambda p: p + 1e-4 * jax.random.normal(
                jax.random.PRNGKey(step), p.shape, p.dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        mgr.save(step, params)
    rep = mgr.storage_report()
    m0 = mgr._manifest["meta_0"]
    m2 = mgr._manifest["meta_2"]
    assert m2["new_bases"] == 0, "drifted ckpt must reuse previous bases"
    assert m2["page_bytes"] < 0.6 * m0["original_bytes"]
    assert rep["compression_ratio"] > 1.5


def test_restart_after_simulated_crash(tmp_path):
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, params, opt)
    # Crash mid-save of step 6: write garbage page without manifest commit.
    with open(mgr.engine._page_path(999), "wb") as f:
        f.write(b"partial garbage")
    del mgr
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.latest_step() == 5
    step, state = mgr2.restore()
    assert step == 5 and state["params"] is not None


def test_async_save(tmp_path):
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, params, opt, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_elastic_restore_different_mesh(tmp_path):
    """Save unsharded → restore and shard onto a different device layout."""
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, params)
    _, state = mgr.restore()
    # Re-shard onto this host's devices (1 device ↔ N devices both fine).
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.distributed import sharding as sh
    from repro.launch import shardings as shd

    with sh.use_mesh(mesh) as ctx:
        specs = shd.param_specs_tree(state["params"], ctx)
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(
                x, jax.sharding.NamedSharding(mesh, s)),
            state["params"], specs,
            is_leaf=lambda x: isinstance(x, np.ndarray))
    flat = jax.tree.leaves(sharded)
    assert all(hasattr(x, "sharding") for x in flat)


def test_flexible_bit_restore(tmp_path):
    """bits=8 restore: approximate params, bounded deviation (fast eval
    replica spin-up per paper §4.3.1)."""
    cfg, params, opt = _small_state()
    mgr = CheckpointManager(str(tmp_path), tolerance=2 ** -24)
    mgr.save(0, params)
    _, exact = mgr.restore()
    _, approx = mgr.restore(bits=8)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(exact["params"]),
            jax.tree_util.tree_leaves_with_path(approx["params"])):
        diff = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        assert diff.mean() < 1e-3


def test_gradient_compression_error_feedback():
    """Quantize→feedback loop: time-averaged gradient is preserved."""
    from repro.distributed.compression import quantize_grad

    rng = np.random.default_rng(0)
    true_g = rng.normal(0, 1e-3, (64, 64)).astype(np.float32)
    err = jnp.zeros_like(jnp.asarray(true_g))
    acc = np.zeros_like(true_g)
    n = 50
    for _ in range(n):
        codes, scale, err = quantize_grad(jnp.asarray(true_g), err, nbit=4)
        acc += np.asarray(codes, np.float32) * float(scale)
    # With error feedback the mean transmitted gradient converges to true.
    np.testing.assert_allclose(acc / n, true_g, atol=2e-5)


def test_cross_pod_sync():
    from repro.distributed.compression import cross_pod_sync, init_error_state

    if len(jax.devices()) < 2:
        mesh = make_mesh((1,), ("pod",))
    else:
        mesh = make_mesh((2,), ("pod",))
    p = mesh.devices.size
    rng = np.random.default_rng(1)
    per_pod = jnp.asarray(rng.normal(0, 1e-3, (p, 32, 16)).astype(np.float32))
    grads = {"w": per_pod}
    errs = init_error_state(grads)
    synced, new_errs = cross_pod_sync(grads, errs, mesh)
    want = np.broadcast_to(np.asarray(per_pod).mean(0), (p, 32, 16))
    # One-shot int8 error ≤ scale/2 ≈ amax/254 (error feedback amortises
    # the rest across steps — see test_gradient_compression_error_feedback).
    amax = float(np.abs(np.asarray(per_pod)).max())
    np.testing.assert_allclose(np.asarray(synced["w"]), want,
                               atol=amax / 254 + 1e-7)
