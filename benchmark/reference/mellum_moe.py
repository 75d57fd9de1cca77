"""Plain reference: a decoder whose layers mix sliding-window and full
attention (YaRN on the full ones) and whose every block routes each token to
the top-k of many SwiGLU experts, of which THIS chip holds a range.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no sorting, no import from the program.  For a layer of type
``layer_types[l]`` on ``x [S, D]``:

1. ``h = rmsnorm(x)``; ``q = h Wq [S, H, K]``, ``k = h Wk``, ``v = h Wv
   [S, G, K]`` (no bias, no normalisation of q and k).
2. Rotary on q and k, pairs ``(2i, 2i+1)`` (the program's interleaved
   pairing; the published half-split differs by a fixed permutation of the
   columns of Wq and Wk, which seeded weights absorb).  ``default``: pair i
   turns by ``pos * theta^(-2i/K)``.  ``yarn``: ``inv_extra_i =
   theta^(-2i/K)``, ``inv_inter_i = inv_extra_i / factor``, ``corr(n) = K
   ln(original / (2 pi n)) / (2 ln theta)``, ``low = floor(corr(beta_fast))``,
   ``high = ceil(corr(beta_slow))`` clipped to ``[0, K - 1]``, ``ramp_i =
   clip((i - low) / (high - low), 0, 1)``, ``inv_i = inv_inter_i ramp_i +
   inv_extra_i (1 - ramp_i)``; cos and sin are multiplied by
   ``attention_factor``.  Static: applied at every length.
3. ``scores = q k^T / sqrt(K)``; query i sees key j iff ``j <= i`` and, on a
   ``sliding_attention`` layer, ``i - j < window``.  Softmax, ``P v``, G KV
   heads shared by H / G query heads each, ``x += concat(heads) Wo``.
4. ``h2 = rmsnorm(x)``; ``p = softmax(h2 Wr)`` over ALL experts; the top-k;
   ``w_e = p_e / sum of the k``; ``x += sum over e among the k and held here
   of w_e Wdown_e (silu(Wgate_e h2) * Wup_e h2)``.  What experts held
   elsewhere would add is left out, as the program leaves it out.
5. Auxiliary loss of a layer (Switch Transformer eq. 4 over all picks):
   ``E * sum_e (share of the S k picks that chose e) * (mean of p_e)``.

After the last layer ``rmsnorm`` and the untied head.  Loss: mean
cross-entropy of ``tokens[1:]`` + ``aux_weight`` x the layers' sum.

Memory: the step check takes this file's whole gradient beside the program's
training state, so attention runs a block of queries at a time under
``jax.checkpoint`` (scores are never kept) and the experts are a loop
(``jax.lax.scan`` over the held range, an expert's hidden products
recomputed in its backward pass): 8.2 GiB with the gradient at 2,048 tokens,
of the 8.8 a chip has beside the cell's training state (builder's compile,
PR 27).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]``; a layer:
``attn_norm [D]``, ``wq [D, H, K]``, ``wk/wv [D, G, K]``, ``wo [H, K, D]``,
``mlp_norm [D]``, ``router [D, E]``, ``w_gate/w_up [held, D, F]``, ``w_down
[held, F, D]``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def inv_freq(head_dim: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """(inverse frequencies ``[K / 2]``, the factor on cos and sin) of one
    ``rope_parameters`` section."""
    theta = float(rope["rope_theta"])
    i = np.arange(head_dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") == "default":
        return extra.astype(np.float32), 1.0
    original = float(rope["original_max_position_embeddings"])

    def corr(rotations: float) -> float:
        return head_dim * math.log(original / (2.0 * math.pi * rotations)) / (2.0 * math.log(theta))

    low = max(math.floor(corr(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope["beta_slow"]))), head_dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = extra / float(rope["factor"]) * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"])


def _rope(x: jax.Array, inv: np.ndarray, factor: float) -> jax.Array:
    """x: [S, heads, K]; position s turns pair (2i, 2i+1) by s * inv[i]."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = factor * jnp.cos(ang)[:, None, :], factor * jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, start: int, window: Optional[int]) -> jax.Array:
    """Queries ``q [B, H, K]`` at positions ``start..`` against all keys."""
    scores = jnp.einsum("shk,thk->hst", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    i = start + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thk->shk", probs, v)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, window: Optional[int]) -> jax.Array:
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for start in range(0, q.shape[0], QUERY_BLOCK):
        block = jax.checkpoint(functools.partial(_attend, start=start, window=window))
        out.append(block(q[start:start + QUERY_BLOCK], k, v))
    return jnp.concatenate(out, axis=0)


def route(h: jax.Array, router: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(probabilities ``[S, E]``, the picks ``[S, k]``, their renormalised
    weights ``[S, k]``)."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    top, picks = jax.lax.top_k(probs, top_k)
    return probs, picks, top / jnp.sum(top, axis=-1, keepdims=True)


def _experts(h: jax.Array, w: Dict[str, jax.Array], top_k: int, first: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h: [S, D] -> (what the held experts add [S, D], the layer's
    auxiliary loss, the picks)."""
    probs, picks, weights = route(h, w["router"], top_k)

    def add_expert(y, expert):  # every held expert on every token, one after the other: plain, not fast
        e, gate, up, down = expert
        mine = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)        # [S]
        return y + mine[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    held = w["w_gate"].shape[0]
    y, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(h), (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"])
    )
    experts = probs.shape[-1]
    share = jnp.mean(jnp.sum(jax.nn.one_hot(picks, experts, dtype=jnp.float32), axis=1), axis=0) / top_k
    return y, experts * jnp.sum(share * jnp.mean(probs, axis=0)), picks


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, layer_types: Sequence[str], window: int,
    rope_parameters: Dict[str, Dict[str, Any]], eps: float, top_k: int, first_expert: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(logits ``[S, V]``, the auxiliary losses summed over layers, the
    picks of every layer ``[L, S, k]``)."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        aux_total, picks = jnp.zeros((), jnp.float32), []
        for layer, layer_type in zip(weights["layers"], layer_types):
            w = {k: v.astype(jnp.float32) for k, v in layer.items()}
            inv, factor = inv_freq(w["wq"].shape[-1], rope_parameters[layer_type])
            h = _rms_norm(x, w["attn_norm"], eps)
            q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"]), inv, factor)
            k = _rope(jnp.einsum("sd,dgk->sgk", h, w["wk"]), inv, factor)
            v = jnp.einsum("sd,dgk->sgk", h, w["wv"])
            att = _attention(q, k, v, window if layer_type == "sliding_attention" else None)
            x = x + jnp.einsum("shk,hkd->sd", att, w["wo"])
            y, aux, chosen = _experts(_rms_norm(x, w["mlp_norm"], eps), w, top_k, first_expert)
            x, aux_total = x + y, aux_total + aux
            picks.append(chosen)
        x = _rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ weights["head"].astype(jnp.float32), aux_total, jnp.stack(picks)


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, *, aux_weight: float, **numerics: Any):
    """Cross-entropy of predicting ``tokens[1:]`` plus ``aux_weight`` x the
    auxiliary losses, and the logits ``[S - 1, V]``."""
    logits, aux, _ = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)) + aux_weight * aux, logits
