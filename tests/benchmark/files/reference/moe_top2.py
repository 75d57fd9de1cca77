"""Plain reference of a test: the dense decoder's block with its MLP
replaced by softmax top-2 routing over a few SwiGLU experts, no token
dropped, plus the Switch auxiliary loss.  Float32 ``jax.numpy`` under matmul
precision "highest", no import from the program.

Router: ``p = softmax(h @ router)`` over all experts; the two largest are
taken and (``renormalise``) divided by their sum; the block adds
``sum_k w_k * expert_k(h)``.  Auxiliary loss of one layer (Switch
Transformer, eq. 4): ``experts * sum_e f_e * P_e`` with ``f_e`` the share of
tokens whose largest probability is expert e's and ``P_e`` the mean of
``p_e``; the training loss is cross-entropy + ``aux_weight`` x the sum over
layers.

Weights: as ``dense_decoder.py`` with, per layer, ``router [D, E]``,
``w_in/w_gate [E, D, F]``, ``w_out [E, F, D]`` in place of the MLP's three.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, K]; position s rotates pair (2i, 2i+1) by s * theta^(-2i/K)."""
    s, _, k = x.shape
    freqs = theta ** (-jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _experts(h: jax.Array, w: Dict[str, jax.Array], renormalise: bool) -> Tuple[jax.Array, jax.Array]:
    """h: [S, D] -> (the block's addition [S, D], this layer's auxiliary loss)."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)                      # [S, E]
    top, idx = jax.lax.top_k(probs, 2)                                    # [S, 2]
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # every expert on every token, then the two chosen: plain, not fast
    every = jnp.einsum(
        "sef,efd->sed",
        jax.nn.silu(jnp.einsum("sd,edf->sef", h, w["w_gate"])) * jnp.einsum("sd,edf->sef", h, w["w_in"]),
        w["w_out"],
    )
    chosen = jnp.take_along_axis(every, idx[:, :, None], axis=1)          # [S, 2, D]
    experts = probs.shape[-1]
    first = jax.nn.one_hot(idx[:, 0], experts, dtype=jnp.float32)
    aux = experts * jnp.sum(jnp.mean(first, axis=0) * jnp.mean(probs, axis=0))
    return jnp.sum(top[:, :, None] * chosen, axis=1), aux


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, rope_theta: float, eps: float, renormalise: bool
) -> Tuple[jax.Array, jax.Array]:
    """(logits ``[S, V]``, the auxiliary losses summed over layers)."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        s = tokens.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
        aux_total = jnp.zeros((), jnp.float32)
        for layer in weights["layers"]:
            w = {k: v.astype(jnp.float32) for k, v in layer.items()}
            h = _rms_norm(x, w["attn_norm"], eps)
            q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"]), rope_theta)
            k = _rope(jnp.einsum("sd,dgk->sgk", h, w["wk"]), rope_theta)
            v = jnp.einsum("sd,dgk->sgk", h, w["wv"])
            group = q.shape[1] // k.shape[1]
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("shk,thk->hst", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
            probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
            x = x + jnp.einsum("shk,hkd->sd", jnp.einsum("hst,thk->shk", probs, v), w["wo"])
            y, aux = _experts(_rms_norm(x, w["mlp_norm"], eps), w, renormalise)
            x, aux_total = x + y, aux_total + aux
        x = _rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ weights["head"].astype(jnp.float32), aux_total


def loss_and_logits(
    weights: Dict[str, Any], tokens: jax.Array, *, rope_theta: float, eps: float, renormalise: bool, aux_weight: float
):
    """Cross-entropy of predicting ``tokens[1:]`` plus ``aux_weight`` x the
    auxiliary losses, and the logits ``[S - 1, V]``."""
    logits, aux = forward(weights, tokens[:-1], rope_theta=rope_theta, eps=eps, renormalise=renormalise)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)) + aux_weight * aux, logits
