"""Plain reference: GLM-5.2's decoder block (``glm_moe_dsa``), multi-head latent
attention that reads only the keys a learned indexer picks (DeepSeek Sparse
Attention's lightning indexer), the picks of a layer with an indexer shared by
the layers after it that hold none (IndexShare), and DeepSeek-V3's sigmoid
router at one group over many SwiGLU experts beside a shared expert, after a
prefix of dense layers; THIS chip holds a range of the experts and a slice of
the vocabulary.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no absorbed products, no import from the program.  What it
shares with DeepSeek-V3's block (RMSNorm, rotary on interleaved pairs, the
router, the experts) it takes from ``reference/deepseek_mla_moe.py`` beside it.
For layer ``l`` on ``x [S, D]``, with ``h = rmsnorm(x)``:

1. Latent attention's projections, as DeepSeek-V3's: ``c_q = rmsnorm(h Wq_a)``,
   ``q = c_q Wq_b -> H heads of [q_nope N | q_rope P]``, ``[c_kv C | k_r P] = h
   Wkv_a``, ``c_kv = rmsnorm(c_kv)``, rotary (``inv_i = theta^(-2i/P)``, pairs
   ``(2i, 2i+1)``, no scaling) on ``q_rope`` and ``k_r``, ``[k_nope_h N | v_h
   V] = c_kv Wkv_b`` a head (``V`` may differ from ``N``), scale ``(N + P)^-0.5``.
2. The indexer of a layer whose ``indexer_types`` entry is ``full``: ``qI = c_q
   WqI -> HI heads of DI``, ``kI = layernorm(h WkI) * g + b [DI]`` (ONE key a
   token), rotary on the first ``P`` of the ``DI`` of both, ``w = h Ww [HI] *
   HI^-0.5 * DI^-0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; the
   picks of query ``t`` are the ``min(t + 1, index_topk)`` positions ``s <= t``
   of largest ``I[t, s]`` (``jax.lax.top_k``: ties to the lower position), an
   explicit ``[S, S]`` mask.  A ``shared`` layer holds no indexer and takes the
   mask of the nearest ``full`` layer before it.
3. ``score_h[t, s] = (q_nope_h . k_nope_h + q_rope_h . k_r) * scale`` where the
   mask holds and ``-inf`` elsewhere; softmax; ``o_h = sum p v_h``; ``x +=
   concat(o_h) Wo``.  Up to ``index_topk`` tokens nothing is left out.
4. The FFN as ``deepseek_mla_moe.py``'s step 3 with ``n_group`` 1: every expert
   is a candidate, the picks are the ``top_k`` largest ``sigmoid(h Wr) + b``.
5. After the last layer ``rmsnorm`` and the untied head over the slice.

Memory: scores and masks are made a block of queries at a time
(``QUERY_BLOCK``), a layer's leaves are upcast as the layer is reached, the held
experts are a loop.  Not here, as not in the program: the multi-token-prediction
module, the Hadamard rotation the published inference code applies to ``qI`` and
``kI`` (orthogonal and applied to both: every ``qI . kI`` is unchanged), FP8.

Weights: as ``deepseek_mla_moe.py``'s, and in a ``full`` layer ``index_wq_b
[R_q, HI, DI]``, ``index_wk [D, DI]``, ``index_k_norm [DI]``, ``index_k_bias
[DI]``, ``index_w [D, HI]``.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_deepseek_mla_moe", os.path.join(os.path.dirname(os.path.abspath(__file__)), "deepseek_mla_moe.py")
)
dsv3 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dsv3)

#: queries a block of the score, mask and attention passes
QUERY_BLOCK = 256


def _by_query_block(fn, *per_query: jax.Array) -> jax.Array:
    """``fn`` on blocks of queries (arrays whose leading axis is the query), the results joined again."""
    s = per_query[0].shape[0]
    block = math.gcd(s, QUERY_BLOCK)
    out = jax.lax.map(lambda a: fn(*a), tuple(a.reshape(s // block, block, *a.shape[1:]) for a in per_query))
    return out.reshape(s, *out.shape[2:])


def _rope_first(x: jax.Array, inv: np.ndarray) -> jax.Array:
    """Rotary on the first ``2 len(inv)`` values of ``x [S, heads, DI]``, the rest as it is."""
    p = 2 * len(inv)
    return jnp.concatenate([dsv3._rope(x[..., :p], inv, 1.0), x[..., p:]], axis=-1)


def index_queries(c_q: jax.Array, w: Dict[str, jax.Array], inv: np.ndarray) -> jax.Array:
    """``qI [S, HI, DI]`` from the normed query latent."""
    return _rope_first(jnp.einsum("sr,rhk->shk", c_q, w["index_wq_b"]), inv)


def index_keys(h: jax.Array, w: Dict[str, jax.Array], inv: np.ndarray, eps: float) -> jax.Array:
    """``kI [S, DI]``: one key a token from the layer's normed input, LayerNorm with weight and bias, rotary."""
    k = h @ w["index_wk"]
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + eps) * w["index_k_norm"] + w["index_k_bias"]
    return _rope_first(k[:, None, :], inv)[:, 0]


def index_weights(h: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    """``w [S, HI]``: a head's weight in a query's scores."""
    heads, dim = w["index_wq_b"].shape[1:]
    return (h @ w["index_w"]) * (heads ** -0.5 * dim ** -0.5)


def index_scores(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array) -> jax.Array:
    """``I [Q, S]`` of a block of queries: ``sum_j w[t, j] relu(qI[t, j] . kI[s])``."""
    return jnp.einsum("qh,qhs->qs", w_i, jax.nn.relu(jnp.einsum("qhd,sd->qhs", q_i, k_i)))


def select(scores: jax.Array, q_pos: jax.Array, topk: int) -> jax.Array:
    """The picks of a block of queries as a mask ``[Q, S]``: the ``min(t + 1,
    topk)`` positions ``s <= t`` of largest score, ties to the lower position."""
    q, s = scores.shape
    causal = jnp.arange(s)[None, :] <= q_pos[:, None]
    _, picks = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))
    return jnp.zeros((q, s), bool).at[jnp.arange(q)[:, None], picks].set(True) & causal


def selection(c_q: jax.Array, h: jax.Array, w: Dict[str, jax.Array], inv: np.ndarray, eps: float, topk: int) -> jax.Array:
    """Step 2: the ``[S, S]`` mask of a layer that holds an indexer."""
    k_i = index_keys(h, w, inv, eps)
    return _by_query_block(
        lambda q_i, w_i, pos: select(index_scores(q_i, w_i, k_i), pos, topk),
        index_queries(c_q, w, inv), index_weights(h, w), jnp.arange(h.shape[0]),
    )


def latent_rows(h: jax.Array, w: Dict[str, jax.Array], inv: np.ndarray, eps: float, latent: int):
    """What a token leaves for later queries: ``(c_kv [S, C] after its norm, k_r [S, P] after rotary)``."""
    kv = h @ w["wkv_a"]
    return dsv3._rms_norm(kv[:, :latent], w["kv_norm"], eps), dsv3._rope(kv[:, None, latent:], inv, 1.0)[:, 0]


def attention(h: jax.Array, w: Dict[str, jax.Array], mask: Optional[jax.Array], *, eps: float, nope: int, latent: int,
              inv: np.ndarray, index_topk: int) -> Any:
    """Steps 1-3 on the normed input ``h [S, D]`` under the mask a ``shared``
    layer was handed (a ``full`` layer makes its own) -> (what attention adds
    ``[S, D]``, the mask)."""
    c_q = dsv3._rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    if "index_wk" in w:
        mask = selection(c_q, h, w, inv, eps, index_topk)
    q = jnp.einsum("sr,rhk->shk", c_q, w["wq_b"])
    q_nope, q_rope = q[..., :nope], dsv3._rope(q[..., nope:], inv, 1.0)
    c_kv, k_r = latent_rows(h, w, inv, eps, latent)
    expanded = jnp.einsum("sc,chk->shk", c_kv, w["wkv_b"])                    # [S, H, N + V]
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scale = (nope + k_r.shape[-1]) ** -0.5

    def attend(q_nope, q_rope, seen):
        scores = (jnp.einsum("qhn,thn->hqt", q_nope, k_nope) + jnp.einsum("qhp,tp->hqt", q_rope, k_r)) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", probs, v)

    out = _by_query_block(attend, q_nope, q_rope, mask)
    return jnp.einsum("shv,hvd->sd", out, w["wo"]), mask


def forward_and_masks(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, nope: int, rope_dim: int, latent: int,
    index_topk: int, top_k: int, scaling: float, first_expert: int,
) -> Tuple[jax.Array, Sequence[jax.Array]]:
    """(logits ``[S, V]`` of one sequence, the ``[S, S]`` mask every layer
    attended under, in order: a ``shared`` layer's is its ``full`` layer's,
    and what the tests hold the program's picks to).  A layer holds an indexer
    where its leaves are there."""
    inv = (rope_theta ** (-2.0 * np.arange(rope_dim // 2, dtype=np.float64) / rope_dim)).astype(np.float32)
    router = {"top_k": top_k, "n_group": 1, "topk_group": 1, "scaling": scaling}
    masks = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        mask = None
        for layer in weights["layers"]:
            sparse = "router" in layer
            w = {k: v if sparse and k in dsv3._STACKS else v.astype(jnp.float32) for k, v in layer.items()}
            att, mask = attention(
                dsv3._rms_norm(x, w["attn_norm"], eps), w, mask, eps=eps, nope=nope, latent=latent, inv=inv, index_topk=index_topk
            )
            masks.append(mask)
            x = x + att
            h = dsv3._rms_norm(x, w["mlp_norm"], eps)
            x = x + (dsv3.expert_layer(h, w, first=first_expert, **router) if sparse else dsv3.swiglu(h, w["w_gate"], w["w_up"], w["w_down"]))
        x = dsv3._rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ weights["head"].astype(jnp.float32), masks


def forward(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any) -> jax.Array:
    """Logits ``[S, V]`` of one sequence."""
    return forward_and_masks(weights, tokens, **numerics)[0]
