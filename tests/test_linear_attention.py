"""The gated delta rule (``ops/delta_rule.py``) and the KDA mixer around it
(``models/linear_attention.py``) against the plain token-by-token
recurrence of ``benchmark/reference/hybrid_linear_decoder.py``, at a tiny
size on the CPU, everything in float32.

Tolerances.  The chunked form and the recurrence are the same sums in
another order (a closed form per chunk of 64 with a triangular solve
against 64 sequential rank-one updates): measured differences are 1e-6
at most on outputs of size ~1 over 200 tokens.  ``TOL = 2e-5`` is twenty
times that and three hundred times under what a bfloat16 state does to
the same outputs (``test_hybrid_decode.py`` has that control).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_linear_decoder as ref
from tensorflowonspark_tpu.models import linear_attention as linear
from tensorflowonspark_tpu.models import transformer as T
from tensorflowonspark_tpu.ops import delta_rule

TOL = 2e-5
H, DK, DV = 3, 16, 8

# log-decays a token: what a trained layer has, a channel that forgets
# everything within a few tokens (alpha ~ 1e-4), and none (alpha = 1)
DECAYS = {"realistic": (-0.7, -0.001), "strong": (-12.0, -5.0),
          "none": (0.0, 0.0)}


def inputs(b, t, decay, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    lo, hi = DECAYS[decay]
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        unit(rng.normal(size=(b, t, H, DK))) / np.sqrt(DK),
        unit(rng.normal(size=(b, t, H, DK))),
        rng.normal(size=(b, t, H, DV)),
        rng.uniform(lo, hi, size=(b, t, H, DK)),
        rng.uniform(0.05, 0.95, size=(b, t, H))))


def recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        o, s = jax.vmap(ref.kda_recurrence)(q, k, v, g, beta)
    return np.asarray(o), np.asarray(s)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_chunked_form_equals_the_recurrence(t, decay):
    args = inputs(2, t, decay, seed=t)
    want_o, want_s = recurrence(*args)
    o, s = delta_rule.gated_delta_chunked(*args)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=TOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=TOL)
    assert np.isfinite(np.asarray(s)).all()


def test_strong_decay_forgets_and_no_decay_keeps():
    """The two ends mean what they say: with alpha ~ 1e-4 the state after
    200 tokens is what the last few wrote; with alpha = 1 and beta = 0
    after the first chunk it is the first chunk's state, untouched."""
    q, k, v, g, beta = inputs(1, 200, "strong")
    _, s_all = delta_rule.gated_delta_chunked(q, k, v, g, beta)
    _, s_tail = delta_rule.gated_delta_chunked(
        q[:, -8:], k[:, -8:], v[:, -8:], g[:, -8:], beta[:, -8:])
    np.testing.assert_allclose(np.asarray(s_all), np.asarray(s_tail),
                               atol=TOL)
    q, k, v, g, beta = inputs(1, 200, "none")
    beta = beta.at[:, 64:].set(0.0)
    _, s_all = delta_rule.gated_delta_chunked(q, k, v, g, beta)
    _, s_head = delta_rule.gated_delta_chunked(
        q[:, :64], k[:, :64], v[:, :64], g[:, :64], beta[:, :64])
    np.testing.assert_allclose(np.asarray(s_all), np.asarray(s_head),
                               atol=1e-7)


def test_the_step_continues_a_chunked_prefix():
    """Chunks from a given state, then single steps: the same outputs and
    the same final state as the recurrence over the whole sequence."""
    q, k, v, g, beta = inputs(2, 150, "realistic", seed=3)
    want_o, want_s = recurrence(q, k, v, g, beta)
    cut = lambda a, lo, hi: a[:, lo:hi]
    _, s = delta_rule.gated_delta_chunked(
        *(cut(a, 0, 70) for a in (q, k, v, g, beta)))
    o, s = delta_rule.gated_delta_chunked(
        *(cut(a, 70, 130) for a in (q, k, v, g, beta)), state=s)
    np.testing.assert_allclose(np.asarray(o), want_o[:, 70:130], atol=TOL)
    for i in range(130, 150):
        o, s = delta_rule.gated_delta_step(
            s, *(a[:, i] for a in (q, k, v, g, beta)))
        np.testing.assert_allclose(np.asarray(o), want_o[:, i], atol=TOL)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=TOL)


# -- the mixer around it --------------------------------------------------------

CFG = T.Config(
    vocab_size=64, dim=32, n_layers=4, n_heads=2, max_seq=256,
    dtype="float32", param_dtype="float32", attn_impl="reference",
    attn_kind="latent", kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
    v_head_dim=8, ffn_kind="swiglu", qk_rotary=False, norm_eps=1e-5,
    linear_layers=(0, 1, 2), linear_heads=H, linear_head_dim=DK,
    linear_rank=8)
SIZES = {"linear_attn_config": {"num_heads": H, "head_dim": DK,
                                "short_conv_kernel_size": 4}}


@pytest.fixture(scope="module")
def mixer():
    return linear.init(jax.random.PRNGKey(5), CFG, jnp.float32)


def hidden(b, t, seed=1):
    y = jax.random.normal(jax.random.PRNGKey(seed), (b, t, CFG.dim))
    return y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True))


def test_the_mixer_equals_the_reference_layer(mixer):
    y = hidden(1, 90)
    out, _s, _c = linear.mix_prefill(mixer, y, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.kda(mixer, y[0], SIZES)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=TOL)


def test_a_padded_batch_gives_each_row_the_state_of_its_true_length(mixer):
    """A bucketed prefill: rows of 5, 64, 77 and 128 real tokens padded to
    128.  Each row's state and convolution history are those of its own
    prompt run alone, unpadded; a prompt shorter than the history keeps
    zeros before its start."""
    lengths = [2, 5, 64, 77, 128]
    y = hidden(len(lengths), 128, seed=2)
    out, s, conv = linear.mix_prefill(mixer, y, CFG,
                                      np.asarray(lengths, np.int32))
    for i, n in enumerate(lengths):
        o1, s1, c1 = linear.mix_prefill(mixer, y[i:i + 1, :n], CFG)
        np.testing.assert_allclose(np.asarray(out[i, :n]),
                                   np.asarray(o1[0]), atol=TOL)
        np.testing.assert_allclose(np.asarray(s[i]), np.asarray(s1[0]),
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(conv[i]), np.asarray(c1[0]),
                                   atol=1e-6)
    assert np.all(np.asarray(conv[0, 0]) == 0) \
        and np.any(np.asarray(conv[0, 1]) != 0)


def test_steps_continue_a_prefill(mixer):
    """Prefill 37 tokens, then 30 single steps against (S, history): the
    outputs of one prefill over all 67."""
    y = hidden(2, 67, seed=4)
    want, want_s, want_c = linear.mix_prefill(mixer, y, CFG)
    _, s, conv = linear.mix_prefill(mixer, y[:, :37], CFG)
    for i in range(37, 67):
        out, s, conv = linear.mix_step(mixer, y[:, i:i + 1], CFG, s, conv)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(want[:, i]), atol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=TOL)
    np.testing.assert_allclose(np.asarray(conv), np.asarray(want_c),
                               atol=1e-6)


def test_random_decays_span_a_realistic_range():
    """``init`` draws ``a_log`` and ``dt_bias`` so that, on normed inputs,
    most channels keep 0.9-0.999 of the state a token, some forget half of
    it or more, none is dead and none is frozen: a cell whose decays were
    all ~1 would never exercise the decay, one whose decays were ~0 would
    have no state worth keeping."""
    cfg = dataclasses.replace(CFG, dim=256, linear_heads=8,
                              linear_head_dim=32, linear_rank=32)
    p = linear.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    y = hidden(4, 64, seed=7).repeat(8, axis=-1)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True))
    window = jnp.zeros((4, 64 + 3, 3 * 8 * 32))
    _q, _k, _v, g, beta = linear._inputs(p, y, window, cfg)
    alpha = np.exp(np.asarray(g)).ravel()
    assert 0.55 < np.mean((alpha > 0.9) & (alpha < 0.999)) < 0.95
    assert 0.005 < np.mean(alpha < 0.5) < 0.15
    assert alpha.min() > 1e-4 and np.mean(alpha > 0.9999) < 0.01
    assert 0.0 < float(beta.min()) and float(beta.max()) < 1.0
    assert p["a_log"].dtype == p["dt_bias"].dtype == jnp.float32
