"""DeepSeek sparse attention (``models/sparse_attention.py``) at a tiny
size on the CPU: a context within ``index_topk`` is dense latent
attention, the query's own group is always read and no group after it,
the program's selection is the reference's wherever the indexer's
margins are clear, the counts the engine reports are what the selection
reads, and the decode step gathers the selected rows only."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import sparse_hybrid_decoder as adapter
from benchmark.reference import sparse_hybrid_decoder as ref
from tensorflowonspark_tpu import ops
from tensorflowonspark_tpu.models import latent_attention as latent
from tensorflowonspark_tpu.models import sparse_attention as sparse
from tensorflowonspark_tpu.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def built():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.3-flash-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    model = dataclasses.replace(adapter.model_config(cfg),
                                attn_impl="reference")
    params = adapter.init_params(model, 21)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    return cfg, model, params, layer


def _inputs(layer, model, t, seed=1, batch=1):
    y = jax.random.normal(jax.random.PRNGKey(seed), (batch, t, model.dim))
    cq = latent.query_latent(layer["attn"], y, model)
    q, rows = latent.project(layer["attn"], y, model, None, None, cq=cq)
    return y, cq, q, rows


def _expanded(layer, q, rows, model):
    return latent.attend_expanded(
        layer["attn"], q, rows, model,
        lambda q, k, v, scale: ops.mha_reference(q, k, v, causal=True,
                                                 scale=scale))


def test_a_context_within_topk_is_dense_latent_attention(built):
    """16 positions against a top-16 selection: every query reads every
    position up to itself, so the layer is dense latent attention, and
    so is the model."""
    _cfg, model, params, layer = built
    y, cq, q, rows = _inputs(layer, model, 16, batch=2)
    got, _keys = sparse.prefill_attention(layer["attn"], layer["index"], q,
                                          rows, cq, y, model)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_expanded(layer, q, rows, model)),
                               atol=2e-5)
    toks = np.random.default_rng(2).integers(1, 128, (1, 16)).astype(
        np.int32)
    dense = dataclasses.replace(model, index_topk=0)
    np.testing.assert_allclose(np.asarray(T.apply(params, toks, model)),
                               np.asarray(T.apply(params, toks, dense)),
                               atol=1e-4)
    # past 16 positions the selection prunes and the two part
    y, cq, q, rows = _inputs(layer, model, 64)
    got, _keys = sparse.prefill_attention(layer["attn"], layer["index"], q,
                                          rows, cq, y, model)
    assert float(jnp.max(jnp.abs(got - _expanded(layer, q, rows, model)))) \
        > 1e-2


def test_the_own_group_is_always_read_and_no_later_one(built):
    _cfg, model, _params, _layer = built
    groups = 12
    tpos = jnp.arange(48)[None]
    # the own group scores LOWEST of all, the later ones highest
    score = jnp.broadcast_to(jnp.arange(groups, dtype=jnp.float32)[::-1],
                             (1, 48, groups))
    score = jnp.where(jnp.arange(groups) == (tpos // 4)[..., None], -1e9,
                      score)
    score = jnp.where(jnp.arange(groups) > (tpos // 4)[..., None], 1e9,
                      score)
    pos, read = sparse.select(score, tpos, model)
    assert pos.shape == (1, 48, 16)
    for t in range(48):
        chosen = set(np.asarray(pos[0, t][read[0, t]]).tolist())
        assert set(range(4 * (t // 4), t + 1)) <= chosen
        assert max(chosen) == t
        # the rest: the largest groups before its own, here the earliest
        want = min(t // 4, 3)
        assert chosen - set(range(4 * (t // 4), t + 1)) \
            == set(range(4 * want))


def test_the_selection_is_the_references_where_the_margins_are_clear(built):
    """The program's selection (bf16 nowhere here: float32 on the CPU)
    against the reference's own, query by query, at the queries whose
    indexer scores leave a clear gap between the last group read and the
    first left out."""
    cfg, model, _params, layer = built
    t = 96
    raw = jax.random.normal(jax.random.PRNGKey(3), (1, t, model.dim))
    # the reference norms its input itself (ln1): the program gets it normed
    y = ops.rmsnorm_reference(raw, jnp.ones((model.dim,)), model.norm_eps)
    cq = latent.query_latent(layer["attn"], y, model)
    pos = jnp.arange(t)[None]
    keys = sparse.index_keys(layer["index"], y, model, pos)
    qi, w = sparse.index_queries(layer["index"], cq, y, model, pos)
    score = sparse.scores(qi, w, sparse.pool_keys(keys, model))
    sel, read = sparse.select(score, pos, model)
    mine = np.zeros((t, t), bool)
    for i in range(t):
        mine[i, np.asarray(sel[0, i][read[0, i]])] = True
    d = ref.dims_of(cfg)
    with jax.default_matmul_precision("highest"):
        q, row, ik, iq, wr = ref.dsa_in(
            layer["attn"], layer["index"], jnp.ones((model.dim,)),
            (raw[0], jnp.arange(t)), d, None)
        ikbar = ik.reshape(-1, 4, ik.shape[-1]).mean(1)
        _o, theirs = ref.dsa_attend(q, iq, wr, 0, row, ikbar,
                                    layer["attn"]["wkvb"], d)
    theirs = np.asarray(theirs)
    s = np.asarray(score[0])
    clear = []
    for i in range(t):
        before = np.sort(s[i, :i // 4])[::-1]
        if len(before) <= 3:
            clear.append(True)
            continue
        clear.append(before[2] - before[3] > 1e-3 * np.abs(before).max())
    clear = np.asarray(clear)
    assert clear.mean() > 0.8
    np.testing.assert_array_equal(mine[clear], theirs[clear])


def test_the_counts_are_what_the_selection_reads(built):
    _cfg, model, _params, _layer = built
    for n in (1, 5, 16, 17, 40, 97):
        tpos = jnp.arange(n)[None]
        groups = -(-n // 4)
        score = jax.random.normal(jax.random.PRNGKey(n), (1, n, groups))
        _pos, read = sparse.select(score, tpos, model)
        assert int(jnp.sum(read)) == sparse.selected_positions([n], model)
    assert sparse.selected_positions([17, 5], model) \
        == sparse.selected_positions([17], model) \
        + sparse.selected_positions([5], model)


def test_the_step_gathers_the_selected_rows_only(built):
    """The decode step's context is ``index_topk`` rows a slot, whatever
    the slot's mapped capacity (here 72 blocks of 4 = 288 positions)."""
    _cfg, model, params, layer = built
    slots, bs, cap = 2, 4, 288
    pool = jax.random.normal(jax.random.PRNGKey(7), (1 + slots * 72, 1, bs,
                                                     model.latent_row))
    ipool = jax.random.normal(jax.random.PRNGKey(8), pool.shape[:3]
                              + (model.index_head_dim,))
    tables = 1 + jnp.arange(slots * 72, dtype=jnp.int32).reshape(slots, 72)
    positions = jnp.asarray([[100], [5]], jnp.int32)
    y = jax.random.normal(jax.random.PRNGKey(9), (slots, 1, model.dim))
    cq = latent.query_latent(layer["attn"], y, model)
    ctx, mask, counters = sparse.step_context(
        layer["index"], cq, y, model, pool, ipool, 0, tables, positions,
        jnp.asarray([True, False]))
    assert ctx.shape == (slots, 16, model.latent_row) and cap > 16
    assert mask.shape == (slots, 1, 1, 16)
    assert int(jnp.sum(mask[0])) == 12 + 100 % 4 + 1
    assert int(jnp.sum(mask[1])) == 6
    # every row read is the pool's row at that position
    blocks = np.asarray(tables)
    for s, p in ((0, 100), (1, 5)):
        got = np.asarray(ctx[s])[np.asarray(mask[s, 0, 0])]
        want = np.asarray(pool)[blocks[s, p // bs], 0, p % bs]
        assert any(np.allclose(r, want) for r in got)
    # the counters count the live slot only
    assert int(counters["dsa_live_positions"]) == 101
    assert int(counters["dsa_selected_positions"]) == 12 + 100 % 4 + 1
    assert int(counters["dsa_scored_groups"]) == 25
