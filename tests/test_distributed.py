"""Distribution-layer tests on the host devices (mesh 1×1 here; the
512-device configuration is exercised by launch/dryrun.py, which must own
the XLA device-count flag)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.launch import shardings as shd
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import init_cache, init_params
from repro.optim import adamw_init

KEY = jax.random.PRNGKey(0)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_param_specs_cover_every_leaf():
    """Every arch's every param leaf gets a valid spec (no fallthroughs that
    shard a mismatched rank)."""
    mesh = _mesh()
    for arch in ("qwen3-8b", "rwkv6-7b", "recurrentgemma-9b", "arctic-480b"):
        cfg = get_config(arch, smoke=True)
        params = init_params(cfg, KEY)
        with sh.use_mesh(mesh) as ctx:
            specs = shd.param_specs_tree(params, ctx)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(params),
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))):
            assert len(tuple(spec)) <= leaf.ndim, (path, spec, leaf.shape)


def test_sharded_train_step_runs():
    """jit with in_shardings on a real (1×1) mesh — the full production
    plumbing (param/opt/batch shardings, microbatching, donation)."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    mesh = _mesh()
    with sh.use_mesh(mesh) as ctx:
        params = init_params(cfg, KEY)
        opt = adamw_init(params)
        p_spec = shd.named(shd.param_specs_tree(params, ctx), mesh)
        o_spec = shd.named(shd.opt_specs_tree(
            opt, shd.param_specs_tree(params, ctx)), mesh)
        batch = {
            "tokens": jnp.zeros((4, 64), jnp.int32),
            "labels": jnp.zeros((4, 64), jnp.int32),
        }
        b_spec = shd.named(shd.batch_specs_tree(batch, ctx), mesh)
        step = jax.jit(make_train_step(cfg, 2),
                       in_shardings=(p_spec, o_spec, b_spec),
                       out_shardings=(p_spec, o_spec, None),
                       donate_argnums=(0, 1))
        params2, opt2, metrics = step(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(opt2["step"]) == 1


def test_sharded_serve_step_runs():
    cfg = get_config("glm4-9b", smoke=True)
    mesh = _mesh()
    with sh.use_mesh(mesh, seq_shard=False, serve=True) as ctx:
        params = init_params(cfg, KEY)
        cache = init_cache(cfg, 2, 64)
        p_spec = shd.named(shd.param_specs_tree(params, ctx), mesh)
        c_spec = shd.named(shd.cache_specs_tree(cache, ctx, cfg.n_kv_heads), mesh)
        step = jax.jit(make_serve_step(cfg),
                       in_shardings=(p_spec, c_spec, None, None),
                       out_shardings=(None, c_spec), donate_argnums=(1,))
        tok, cache = step(params, cache, {"tokens": jnp.zeros((2, 1), jnp.int32)},
                          jnp.int32(0))
        assert tok.shape == (2,)


def test_fit_spec_divisibility():
    """fit_spec drops/replaces axes whose size doesn't divide the dim."""
    mesh = make_mesh((1, 1), ("data", "model"))
    # These mesh axes are size 1 → everything divides; test the logic
    # directly with a fake 16×16 shape table instead.
    from repro.launch.shardings import _fits

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)

    assert _fits(P("data", "model"), (32, 32), FakeMesh)
    assert not _fits(P("data", "model"), (32, 8), FakeMesh)
    assert not _fits(P(("data", "model"),), (64,), FakeMesh)
    assert _fits(P(("data", "model"),), (256,), FakeMesh)


def test_collective_stats_parser():
    hlo = """
  %ag = bf16[16,256]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), replica_groups=[2,8]<=[16], to_apply=%sum
  %rs = f32[4,32]{1,0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %cp = bf16[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %done = bf16[16,256]{1,0} all-gather-done(%ag)
"""
    stats = collective_stats(hlo, 16)
    assert stats["count"] == 4
    ag = 16 * 256 * 2 * 3 / 4
    ar = 2 * (128 * 4 + 64 * 4) * 7 / 8
    rs = 4 * 32 * 4 * 1
    cp = 8 * 8 * 2
    np.testing.assert_allclose(stats["all-gather"], ag)
    np.testing.assert_allclose(stats["all-reduce"], ar)
    np.testing.assert_allclose(stats["reduce-scatter"], rs)
    np.testing.assert_allclose(stats["collective-permute"], cp)


def test_data_pipeline_determinism_and_sharding():
    from repro.data import SyntheticLM

    data = SyntheticLM(1024, seed=3)
    b1 = data.batch(7, 16, 32)
    b2 = data.batch(7, 16, 32)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # Shards partition the work deterministically.
    s0 = data.batch(7, 16, 32, shard=0, n_shards=4)
    assert s0["tokens"].shape == (4, 32)
    # Labels are next-token aligned.
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
