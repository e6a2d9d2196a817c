"""Pipeline parallelism + MoE expert parallelism on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu.models import layers as L
from tensorflowonspark_tpu.models import moe
from tensorflowonspark_tpu.parallel import (
    pipeline_apply,
    stack_stage_params,
    stage_sharding,
)


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _stages(key, n, dim):
    ks = jax.random.split(key, n)
    return [
        {"w": jax.random.normal(k, (dim, dim)) / np.sqrt(dim),
         "b": jnp.zeros((dim,))}
        for k in ks
    ]


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 8)])
def test_pipeline_matches_sequential(eight_devices, n_stages, n_micro):
    mesh = Mesh(np.array(eight_devices[:n_stages]), ("pp",))
    dim, batch = 16, 16
    stages = _stages(jax.random.PRNGKey(0), n_stages, dim)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, dim))

    # sequential reference
    ref = x
    for p in stages:
        ref = _stage_fn(p, ref)

    stacked = stack_stage_params(stages)
    stacked = jax.device_put(stacked, stage_sharding(mesh, stacked))
    out = jax.jit(
        lambda sp, x: pipeline_apply(
            _stage_fn, sp, x, mesh=mesh, n_microbatches=n_micro
        )
    )(stacked, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_is_differentiable(eight_devices):
    mesh = Mesh(np.array(eight_devices[:2]), ("pp",))
    dim = 8
    stages = _stages(jax.random.PRNGKey(0), 2, dim)
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, dim))

    def loss(sp, x):
        y = pipeline_apply(_stage_fn, sp, x, mesh=mesh, n_microbatches=4)
        return jnp.sum(y ** 2)

    g = jax.jit(jax.grad(loss))(stacked, x)

    def loss_seq(sp, x):
        y = x
        for i in range(2):
            y = _stage_fn(jax.tree.map(lambda p: p[i], sp), y)
        return jnp.sum(y ** 2)

    g_ref = jax.grad(loss_seq)(stacked, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("held", [None, 2])
def test_moe_forward_and_balance_statistics(held):
    """The expert layer's forward pass, its balance statistics, and
    gradients to router and experts — holding every expert, and holding
    2 of 4 (a share)."""
    params = moe.init(jax.random.PRNGKey(0), dim=16, hidden=32,
                      num_experts=4, num_held=held, num_shared=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, stats = moe.apply(params, x, top_k=2, routed_scale=2.5)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
    # every token makes 2 picks; load is their share per expert
    assert int(stats["picks"]) == 2 * 8 * 2
    np.testing.assert_allclose(float(jnp.sum(stats["load"])), 1.0, rtol=1e-6)
    assert stats["importance"].shape == (4,)
    assert int(stats["dropped"]) == 0
    if held is None:
        assert int(stats["picks_held"]) == int(stats["picks"])
    else:
        assert 0 < int(stats["picks_held"]) < int(stats["picks"])
        assert int(stats["experts_touched"]) <= held

    # gradients flow to router and experts
    def loss(p):
        y, stats = moe.apply(p, x, top_k=2, routed_scale=2.5)
        return jnp.sum(y ** 2) + jnp.sum(stats["importance"] ** 2)

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["wg"]).sum()) > 0
    assert float(jnp.abs(g["shared_wd"]).sum()) > 0


def test_moe_expert_sharded_on_mesh(eight_devices):
    """Experts on the ``ep`` axis of the 8 virtual devices equal the
    unsharded result."""
    mesh = Mesh(np.array(eight_devices).reshape(2, 4), ("data", "ep"))
    params = moe.init(jax.random.PRNGKey(0), dim=16, hidden=64,
                      num_experts=8, num_shared=1)
    specs = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        moe.param_specs(ep_axis="ep", shared=True),
        is_leaf=lambda s: isinstance(s, P),
    )
    sharded = jax.device_put(params, specs)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16)),
        NamedSharding(mesh, P("data")),
    )
    apply = lambda p, x: moe.apply(p, x, top_k=2, routed_scale=2.5)[0]
    y = jax.jit(apply)(sharded, x)
    ref = apply(params, jax.device_get(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
