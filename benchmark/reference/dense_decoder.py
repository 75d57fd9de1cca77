"""Plain reference: a dense decoder-only transformer, forward and loss.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and no import from the program.  It follows the published
description of the Mistral-7B and InternLM2 block (pre-norm RMSNorm,
grouped-query attention with rotary embeddings, SwiGLU, untied head).

Departures from the published models, both forced by the program it is the
yardstick of, and both stated in the configuration files:

- rotary pairs are the interleaved ``(2i, 2i+1)`` and not the published
  half-split ``(i, i + d/2)``.  The two differ by a fixed permutation of the
  columns of ``wq`` and ``wk``, which seeded random weights absorb.
- ``rms_norm_eps`` is what the configuration file states as run (the program
  fixes 1e-6 where the sources say 1e-5).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and per layer
``attn_norm [D]``, ``wq [D, H, K]``, ``wk/wv [D, G, K]``, ``wo [H, K, D]``,
``mlp_norm [D]``, ``w_gate/w_up [D, F]``, ``w_down [F, D]``.
"""

from __future__ import annotations

from typing import Any, Dict

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


def forward(weights: Dict[str, Any], tokens: jax.Array, *, rope_theta: float, eps: float) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        s = tokens.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
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
            att = jnp.einsum("hst,thk->shk", probs, v)
            x = x + jnp.einsum("shk,hkd->sd", att, w["wo"])
            h = _rms_norm(x, w["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        x = _rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ weights["head"].astype(jnp.float32)


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, *, rope_theta: float, eps: float):
    """Mean cross-entropy of predicting ``tokens[1:]`` from ``tokens[:-1]``,
    and the logits ``[S - 1, V]`` it was taken from."""
    logits = forward(weights, tokens[:-1], rope_theta=rope_theta, eps=eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
