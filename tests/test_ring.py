"""Sequence parallelism: ring / Ulysses attention vs single-device reference,
on the 8-virtual-device CPU mesh (the multi-chip test fixture)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.parallel import (
    ring_attention,
    sequence_parallel_attention,
    ulysses_attention,
)

from tensorflowonspark_tpu.parallel.ring import shard_map


def _qkv(key, b, s, h, d):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, s, h, d)) for k in ks)


def _seq_mesh(devs, n=4):
    return Mesh(np.array(devs[:n]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_matches_reference(eight_devices, impl, causal):
    mesh = _seq_mesh(eight_devices)
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 64, 4, 8)
    ref = ops.mha_reference(q, k, v, causal=causal)

    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    out = jax.jit(
        shard_map(
            lambda q, k, v: fn(q, k, v, "seq", causal=causal),
            mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads_match(eight_devices):
    mesh = _seq_mesh(eight_devices)
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 32, 2, 8)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
        mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    # under jit, as the trainers run it: the eager gradient of a
    # shard_map'd ring dispatches op by op and takes twenty times as long
    g1 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(
            ops.mha_reference(q, k, v, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_sequence_parallel_attention_wrapper(eight_devices):
    # 2x2x2 mesh: data x seq x model — the wrapper must place specs on
    # the right axes and return the same sharding it consumed.
    mesh = Mesh(np.array(eight_devices).reshape(2, 2, 2),
                ("data", "seq", "model"))
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 32, 4, 8)
    call = sequence_parallel_attention(mesh, "ring", causal=True)
    spec = NamedSharding(mesh, P("data", "seq", "model", None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = jax.jit(call)(qs, ks, vs)
    ref = ops.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_zigzag_permutation_roundtrip():
    from tensorflowonspark_tpu.parallel import (
        inverse_permutation, zigzag_permutation,
    )

    perm = zigzag_permutation(32, 4)  # 8 stripes of 4
    assert sorted(np.asarray(perm).tolist()) == list(range(32))
    # device 0's shard = stripes (0, 7), device 1's = (1, 6), ...
    assert np.asarray(perm)[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]
    inv = inverse_permutation(perm)
    x = np.arange(32)
    np.testing.assert_array_equal(x[np.asarray(perm)][np.asarray(inv)], x)


@pytest.mark.parametrize("causal", [False, True])
def test_zigzag_ring_matches_reference(eight_devices, causal):
    from tensorflowonspark_tpu.parallel import (
        inverse_permutation, zigzag_permutation, zigzag_ring_attention,
    )

    mesh = _seq_mesh(eight_devices)
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 64, 4, 8)
    ref = ops.mha_reference(q, k, v, causal=causal)

    perm = zigzag_permutation(64, 4)
    inv = inverse_permutation(perm)
    zz = jax.jit(
        shard_map(
            lambda q, k, v: zigzag_ring_attention(
                q, k, v, "seq", causal=causal),
            mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
        )
    )
    out = zz(q[:, perm], k[:, perm], v[:, perm])[:, inv]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_zigzag_ring_grads_match(eight_devices):
    from tensorflowonspark_tpu.parallel import (
        inverse_permutation, zigzag_permutation, zigzag_ring_attention,
    )

    mesh = _seq_mesh(eight_devices)
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 32, 2, 8)
    perm = zigzag_permutation(32, 4)
    inv = inverse_permutation(perm)

    zz = shard_map(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "seq", causal=True),
        mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )

    def loss_zz(q, k, v):
        return jnp.sum(zz(q[:, perm], k[:, perm], v[:, perm])[:, inv] ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ops.mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_zigzag_end_to_end_lm_training_matches(eight_devices):
    """Production zigzag: permuted tokens + explicit positions/labels +
    zigzag attention must give the SAME loss and gradients as the
    standard contiguous path — no per-layer gathers needed."""
    from tensorflowonspark_tpu.models import transformer
    from tensorflowonspark_tpu.parallel import (
        sequence_parallel_attention, zigzag_permutation,
    )

    mesh = Mesh(np.array(eight_devices[:4]).reshape(1, 4, 1),
                ("data", "seq", "model"))
    cfg = transformer.Config(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                             max_seq=32, dtype="float32",
                             attn_impl="reference")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)

    base_loss, base_grads = jax.value_and_grad(transformer.loss_fn)(
        params, tokens, cfg)

    perm = zigzag_permutation(32, 4)
    toks_p, labels_p, positions = transformer.zigzag_lm_batch(tokens, perm)
    zz_attn = sequence_parallel_attention(mesh, "zigzag", causal=True)

    def zz_loss(p, t):
        return transformer.loss_fn(
            p, t, cfg, attn_fn=zz_attn, labels=labels_p,
            positions=positions)

    zz_l, zz_grads = jax.jit(jax.value_and_grad(zz_loss))(params, toks_p)
    np.testing.assert_allclose(float(zz_l), float(base_loss), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        zz_grads, base_grads)
