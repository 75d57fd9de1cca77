"""The indexer's score pass and latent attention over its picks
(``ops/paged_attention.py``: ``paged_index_scores``, ``index_topk``,
``index_topk_mask``, ``paged_picked_attention``, the mask of
``paged_chunk_attention``) in every form the program has, the Pallas kernel in
interpret mode, against the gathered-table oracle of the serving forward
(``models/cache_kinds.py _latent_attend_table``) under the reference's mask."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.models.cache_kinds import Rows, _latent_attend_table, _latent_split, _table_mask
from determined_tpu.ops import paged_attention as paged
from tests.model_cases import PAGED_EDGES, check_copy_schedule, reference_module

reference = reference_module("glm_moe_dsa")

BLOCK, COLS, HEADS, DIM, TOPK = 8, 12, 4, 128, 16


@pytest.fixture(scope="module")
def keys():
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(2, 64, BLOCK, DIM)), jnp.float32)
    return pool, rng


def picks_mask(picks, valid, keys):
    """The picks of ``index_topk`` [b, s, k] as a mask [b, s, keys], by a scatter: the oracle of ``index_topk_mask``."""
    mask = np.zeros((*picks.shape[:2], keys), bool)
    b, s, k = np.nonzero(np.asarray(valid))
    mask[b, s, np.asarray(picks)[b, s, k]] = True
    return mask


def _lanes(rng, contexts):
    b = len(contexts)
    tables = jnp.asarray(rng.permutation(np.arange(1, 64))[: b * COLS].reshape(b, COLS), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, HEADS, DIM)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, HEADS)), jnp.float32)
    return tables, q, w, jnp.asarray(contexts, jnp.int32) - 1


@pytest.mark.parametrize("edge", sorted(PAGED_EDGES))
@pytest.mark.parametrize("tile_blocks", [2, 5])
def test_the_index_score_kernel_scores_a_lanes_live_keys_and_copies_no_other_block(keys, edge, tile_blocks):
    """Against the reference's own statement of the scores (``sum_j w_j relu(q_j
    . k)``) on the gathered keys; dead positions and idle lanes read NEG_INF; the
    walk copies the live blocks alone (``check_copy_schedule``: NaN everywhere else)."""
    pool, rng = keys
    contexts = PAGED_EDGES[edge]
    tables, q, w, pos = _lanes(rng, contexts)
    run = lambda p: paged.paged_index_scores(q, w, p, 1, tables, pos, impl="kernel_interpret", tile_blocks=tile_blocks)  # noqa: E731
    got = check_copy_schedule(run, (pool,), 1, tables, contexts, BLOCK)
    gathered = np.asarray(pool)[1][np.asarray(tables)].reshape(len(contexts), COLS * BLOCK, DIM)
    for lane, n in enumerate(contexts):
        want = np.asarray(reference.index_scores(q[lane][None], w[lane][None], jnp.asarray(gathered[lane][:n])))[0]
        np.testing.assert_allclose(got[lane, :n], want, atol=2e-4)
        assert (got[lane, n:] <= paged.NEG_INF / 2).all()
    np.testing.assert_allclose(got, np.asarray(paged.paged_index_scores(q, w, pool, 1, tables, pos, impl="jnp")), atol=2e-4)


def test_the_exact_top_k_breaks_ties_towards_the_lower_position_and_marks_what_is_no_pick():
    scores = jnp.asarray([[[1.0, 3.0, 3.0, 0.5, 3.0, 2.0]], [[5.0, 1.0, 0.0, 0.0, 0.0, 0.0]]])
    seen = jnp.asarray([[[True] * 6], [[True, True, False, False, False, False]]])
    picks, valid = paged.index_topk(scores, seen, 3)
    assert np.asarray(picks)[0, 0].tolist() == [1, 2, 4] and np.asarray(valid)[0, 0].all()
    assert np.asarray(picks)[1, 0, :2].tolist() == [0, 1] and np.asarray(valid)[1, 0].tolist() == [True, True, False]
    mask = picks_mask(picks, valid, 6)
    assert mask[0, 0].tolist() == [False, True, True, False, True, False] and mask[1, 0].tolist() == [True, True] + [False] * 4
    assert paged.index_topk(scores, seen, 9)[0].shape == (2, 1, 6)  # never more picks than keys
    np.testing.assert_array_equal(np.asarray(paged.index_topk_mask(scores, seen, 3)), mask)


@pytest.mark.parametrize("topk", [1, 17, 64, 400])
def test_the_selection_as_a_mask_is_the_top_ks_pick_for_pick_and_tie_for_tie(topk):
    """``index_topk_mask`` (no sort, no scatter) against ``index_topk`` and its scatter: scores with runs of equal
    values across the cut (zeros, a repeated negative, scores rounded to tenths), queries that see fewer keys than
    they may pick, NEG_INF among the scores, and a query that sees nothing."""
    rng = np.random.default_rng(topk)
    scores = rng.normal(size=(2, 9, 300)).astype(np.float32)
    scores[0, 0, :120], scores[1, 3, 10:250], scores[0, 2] = 0.0, -1.5, np.round(scores[0, 2], 1)
    scores[1, 5, ::3] = paged.NEG_INF
    pos = rng.integers(0, 300, size=(2, 9))
    seen = (jnp.arange(300)[None, None, :] <= jnp.asarray(pos)[..., None]).at[1, 8].set(False)
    want = picks_mask(*paged.index_topk(jnp.asarray(scores), seen, topk), 300)
    got = jax.jit(lambda s, m: paged.index_topk_mask(s, m, topk))(jnp.asarray(scores), seen)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert not np.asarray(got)[1, 8].any() and int(np.asarray(got)[0, 0].sum()) == min(topk, int(pos[0, 0]) + 1)


@pytest.mark.parametrize("contexts", [(70, 0, 13, 96), (16, 17, 0, 1), (96, 96, 96, 96), (0, 0, 40, 0)], ids=["mixed", "at_topk", "full", "one_live"])
def test_attention_over_the_picks_is_the_gathered_table_under_the_references_mask(keys, contexts):
    """Lanes under, at and over ``index_topk`` and idle lanes between them:
    the gathered rows' attention against ``_latent_attend_table`` told the
    reference's mask (its exact top-k of the same scores), idle lanes zeros."""
    _, rng = keys
    width, latent, rope, heads = 256, 128, 16, 8
    pool = jnp.asarray(rng.normal(size=(3, 64, BLOCK, width)), jnp.float32).at[..., latent + rope:].set(0.0)
    tables, _, _, pos = _lanes(rng, contexts)
    live = np.asarray(pos) >= 0
    scores = jnp.asarray(rng.normal(size=(4, COLS * BLOCK)), jnp.float32)
    seen = (jnp.arange(COLS * BLOCK)[None, :] <= pos[:, None])[:, None]
    picks, valid = paged.index_topk(scores[:, None], seen, TOPK)
    q_lat = jnp.asarray(rng.normal(size=(4, heads, 1, latent)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(4, heads, 1, rope)), jnp.float32)
    q = jnp.pad(jnp.concatenate([q_lat, q_rope], -1)[:, :, 0], ((0, 0), (0, 0), (0, width - latent - rope)))
    got = np.asarray(paged.paged_picked_attention(q, pool, 2, tables, picks[:, 0], valid[:, 0], scale=0.1, value_dim=latent))
    # the oracle: identity projections, so that it returns the latent-space result itself
    cfg = types.SimpleNamespace(kv_lora_rank=latent, qk_rope_head_dim=rope, qk_nope_head_dim=latent, attn_scale=0.1, dtype=jnp.float32)
    eye = jnp.concatenate([jnp.eye(latent)[:, None, :].repeat(heads, 1), jnp.eye(latent)[:, None, :].repeat(heads, 1)], -1)
    assert np.allclose(np.asarray(_latent_split(cfg, eye, q_lat)[0]), np.asarray(q_lat))
    rows = Rows(pos[:, None], tables, pos >= 0, None, BLOCK)
    want_mask = np.stack([np.asarray(reference.select(scores[b][None], pos[b][None], TOPK))[0] for b in range(4)])
    mask = picks_mask(picks, valid, COLS * BLOCK)
    np.testing.assert_array_equal(mask[:, 0] & live[:, None], want_mask & live[:, None])
    table = _latent_attend_table(cfg, tables, _table_mask(rows))
    want = np.asarray(table(q_lat, q_rope, None, None, eye, {"kv": pool}, 2, jnp.asarray(mask)))[:, 0]
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not got[~live].any()
    assert [int(m.sum()) for m, on in zip(want_mask, live) if on] == [min(n, TOPK) for n in contexts if n]
    over = [b for b, n in enumerate(contexts) if n > TOPK]
    if over:  # the mask leaves rows out
        assert not np.allclose(want[over], np.asarray(table(q_lat, q_rope, None, None, eye, {"kv": pool}, 2))[over, 0], atol=1e-3)


def test_the_chunk_walk_under_a_mask_sees_the_picked_keys_alone(keys):
    """``paged_chunk_attention`` told a ``[chunk, keys]`` mask a lane against the
    dense softmax under that mask and the causal one."""
    _, rng = keys
    width, latent, heads, s = 128, 64, 4, 16
    pool = jnp.asarray(rng.normal(size=(1, 40, BLOCK, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:12].reshape(2, 6), jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 1, heads, s, width)), jnp.float32)
    mask = jnp.asarray(rng.random(size=(2, s, 48)) < 0.4).at[:, :, 0].set(True)
    rows = np.asarray(pool)[0][np.asarray(tables)].reshape(2, 48, width)
    for chunk in (0, 1, 2):
        got = np.asarray(paged.paged_chunk_attention(q, pool, None, 0, tables, chunk, scale=0.1, value_dim=latent, mask=mask))[:, 0]
        q_pos = chunk * s + np.arange(s)
        seen = (np.arange(48)[None, :] <= q_pos[:, None])[None] & np.asarray(mask)
        logits = np.where(seen[:, None], np.einsum("bhqw,bkw->bhqk", np.asarray(q)[:, 0], rows) * 0.1, -1e30)
        want = np.einsum("bhqk,bkc->bhqc", np.asarray(jax.nn.softmax(jnp.asarray(logits), -1)), rows[..., :latent])
        np.testing.assert_allclose(got, want, atol=2e-5)
