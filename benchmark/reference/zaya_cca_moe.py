"""Plain reference: ZAYA1's block.  Attention inside a compressed, convolved
latent (CCA; Zyphra, arXiv:2510.04476), a top-1 router that is an MLP
carrying a state from layer to layer, learned residual scaling (the ZAYA1
report, arXiv:2511.17127), a tied head; of each layer's experts THIS chip
holds a range.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no sorting, no import from the program.  ``D`` the model's width,
``hq`` query heads over ``hk`` K/V heads of ``dh`` values, ``g = hq / hk``,
``R`` the router's width, ``E`` experts; ``t`` a position, rows before the
sequence's first are zero; ``norm`` is RMSNorm.  A layer on ``x [S, D]``:

1. CCA sublayer, ``u = norm(x)``:
   ``q~ = u Wq [S, hq, dh]``, ``k~ = u Wk [S, hk, dh]``;
   the q-k mean: ``mq[t, h] = (q~[t, h] + k~[t, h // g]) / 2``, ``mk[t, j]``
   the mean of ``mq[t, h]`` over the ``g`` heads ``h`` of group ``j``;
   ``z = concat(q~, k~)``, ``C = (hq + hk) dh`` channels; a depthwise causal
   convolution of ``T0`` taps, ``z1[t, c] = sum_i a[c, i] z[t - (T0 - 1) + i, c]
   + a0[c]``; then one of ``T1`` taps that mixes the ``dh`` channels of each
   head, ``z2[t, c] = sum_i sum_{c' in head(c)} B[c, c', i] z1[t - (T1 - 1) + i,
   c'] + b0[c]`` (``z1`` before the first position is zero too);
   ``q = z2[:, :hq dh] + mq``, ``k = z2[:, hq dh:] + mk``;
   ``q <- sqrt(dh) q / |q|`` a head, ``k <- exp(tau[j]) sqrt(dh) k / |k|`` a
   K/V head ``j``;
   rotary on the first ``rotary`` values of every head of q and k, pairs
   ``(2i, 2i+1)``, pair ``i`` turning by ``t theta^(-2i / rotary)``;
   the value shift: the first ``hk - hk // 2`` K/V heads take ``u[t] Wv1``,
   the others ``u[t - 1] Wv2``;
   ``o = causal_softmax(q k^T / sqrt(dh)) v``, query head ``h`` on K/V head
   ``h // g``; the sublayer's output is ``o Wo``.
2. ``x <- (x + br) ar + (f + bf) af`` with the sublayer's four vectors.
3. Expert sublayer, ``w = norm(x)``: ``r = w Wd + bd [S, R]``; past the
   first layer ``r <- r + gamma r_before`` (``r_before`` the layer before's
   ``r`` after ITS mix; this ``r`` is what is handed on);
   ``p = softmax(W3 gelu(W2 gelu(W1 norm_R(r) + b1) + b2))`` over all ``E``
   (the exact gelu); ``e* = argmax(p + beta)``: ``beta`` picks and never
   weighs; output ``p[e*] Wdown_e* (silu(Wgate_e* w) * Wup_e* w)`` where
   ``e*`` is held here, else nothing.  Auxiliary term of the layer (Switch
   Transformer eq. 4): ``E sum_e (share of the S picks on e) (mean of p_e)``.
4. The second residual scaling, as in 2.

After the last layer ``norm`` and the tied head (the embedding's transpose).
Loss: mean cross-entropy of ``tokens[1:]`` + ``aux_weight`` x the layers' sum.

Memory: the step check takes this file's whole gradient beside the program's
training state, so attention runs a block of queries at a time under
``jax.checkpoint`` (scores are never kept) and the experts are a loop
(``jax.lax.scan`` over the held range, an expert's hidden products
recomputed in its backward pass).

Weights: ``embed [V, D]``, ``final_norm [D]``; a layer: ``attn_norm [D]``,
``wq [D, hq, dh]``, ``wk [D, hk, dh]``, ``wv1 [D, hk - hk // 2, dh]``, ``wv2
[D, hk // 2, dh]``, ``conv0 [C, T0]`` (``a``), ``conv0_bias [C]``, ``conv1 [C,
dh, T1]`` (``B``: the second index counts inside the head), ``conv1_bias
[C]``, ``tau [hk]``, ``wo [hq, dh, D]``, ``attn_scaling`` and ``mlp_scaling``
(each ``res_bias``, ``res_scale``, ``out_bias``, ``out_scale`` of ``[D]``),
``mlp_norm [D]``, ``router`` (``down [D, R]``, ``down_bias``, ``mix``
(``gamma``), ``norm`` of ``[R]``, ``w1``, ``w2 [R, R]``, ``b1``, ``b2 [R]``,
``w3 [R, E]``, ``beta [E]``), ``w_gate/w_up [held, D, F]``, ``w_down [held, F, D]``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _gelu(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def _before(x: jax.Array, steps: int) -> jax.Array:
    """``x [S, ...]`` as it was ``steps`` positions earlier; zeros before the first."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def _rope_partial(x: jax.Array, rotary: int, theta: float) -> jax.Array:
    """x: [S, heads, dh]; the first ``rotary`` values of every head turn."""
    i = np.arange(rotary // 2, dtype=np.float64)
    inv = (float(theta) ** (-2.0 * i / rotary)).astype(np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0:rotary:2], x[..., 1:rotary:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape[:-1] + (rotary,))
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, start: int) -> jax.Array:
    """Queries ``q [B, H, dh]`` at positions ``start..`` against all keys, causal."""
    scores = jnp.einsum("shk,thk->hst", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = jnp.arange(k.shape[0])[None, :] <= start + jnp.arange(q.shape[0])[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thk->shk", probs, v)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for start in range(0, q.shape[0], QUERY_BLOCK):
        block = jax.checkpoint(functools.partial(_attend, start=start))
        out.append(block(q[start:start + QUERY_BLOCK], k, v))
    return jnp.concatenate(out, axis=0)


def cca(u: jax.Array, w: Dict[str, jax.Array], *, rotary: int, theta: float) -> jax.Array:
    """The CCA sublayer on the normed input ``u [S, D]`` -> ``[S, D]``."""
    s = u.shape[0]
    hq, dh = w["wq"].shape[1:]
    hk = w["wk"].shape[1]
    g = hq // hk
    q0 = jnp.einsum("sd,dhk->shk", u, w["wq"])
    k0 = jnp.einsum("sd,dhk->shk", u, w["wk"])
    mq = (q0 + jnp.repeat(k0, g, axis=1)) / 2.0
    mk = jnp.mean(mq.reshape(s, hk, g, dh), axis=2)
    z = jnp.concatenate([q0, k0], axis=1).reshape(s, (hq + hk) * dh)                    # [S, C]
    t0, t1 = w["conv0"].shape[-1], w["conv1"].shape[-1]
    z1 = sum(w["conv0"][:, i] * _before(z, t0 - 1 - i) for i in range(t0)) + w["conv0_bias"]
    z1 = z1.reshape(s, hq + hk, dh)
    mix = w["conv1"].reshape(hq + hk, dh, dh, t1)                                       # [head, c, c', tap]
    z2 = sum(jnp.einsum("shd,hcd->shc", _before(z1, t1 - 1 - i), mix[..., i]) for i in range(t1))
    z2 = z2 + w["conv1_bias"].reshape(hq + hk, dh)
    q, k = z2[:, :hq] + mq, z2[:, hq:] + mk
    length = lambda x: jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))  # noqa: E731
    q = np.sqrt(dh) * q / length(q)
    k = jnp.exp(w["tau"])[None, :, None] * np.sqrt(dh) * k / length(k)
    q, k = _rope_partial(q, rotary, theta), _rope_partial(k, rotary, theta)
    v = jnp.concatenate(
        [jnp.einsum("sd,dhk->shk", u, w["wv1"]), jnp.einsum("sd,dhk->shk", _before(u, 1), w["wv2"])], axis=1
    )
    return jnp.einsum("shk,hkd->sd", _attention(q, k, v), w["wo"])


def rescale(x: jax.Array, f: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    return (x + w["res_bias"]) * w["res_scale"] + (f + w["out_bias"]) * w["out_scale"]


def route(h: jax.Array, w: Dict[str, jax.Array], before: Optional[jax.Array], eps: float):
    """(probabilities ``[S, E]``, the pick ``[S]``, its weight ``[S]``, the state handed on ``[S, R]``)."""
    r = h @ w["down"] + w["down_bias"]
    if before is not None:
        r = r + w["mix"] * before
    hidden = _gelu(_rms_norm(r, w["norm"], eps) @ w["w1"] + w["b1"])
    hidden = _gelu(hidden @ w["w2"] + w["b2"])
    probs = jax.nn.softmax(hidden @ w["w3"], axis=-1)
    pick = jnp.argmax(probs + w["beta"][None, :], axis=-1)
    return probs, pick, jnp.take_along_axis(probs, pick[:, None], axis=-1)[:, 0], r


def _experts(h: jax.Array, w: Dict[str, Any], before: Optional[jax.Array], first: int, eps: float):
    """h: [S, D] -> (what the held experts add [S, D], the layer's auxiliary
    loss, the picks, their weights, the router's state)."""
    probs, pick, weight, r = route(h, w["router"], before, eps)

    def add_expert(y, expert):  # every held expert on every token, one after the other: plain, not fast
        e, gate, up, down = expert
        mine = jnp.where(pick == first + e, weight, 0.0)                                 # [S]
        return y + mine[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    held = w["w_gate"].shape[0]
    y, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(h), (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"])
    )
    experts = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(pick, experts, dtype=jnp.float32), axis=0)
    return y, experts * jnp.sum(share * jnp.mean(probs, axis=0)), pick, weight, r


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, rotary: int, rope_theta: float, eps: float, first_expert: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(logits ``[S, V]``, the auxiliary losses summed over layers, every
    layer's picks ``[L, S]`` and their weights ``[L, S]``)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)  # noqa: E731
        embed = weights["embed"].astype(jnp.float32)
        x = embed[tokens]
        aux_total, picks, pick_weights, r = jnp.zeros((), jnp.float32), [], [], None
        for layer in weights["layers"]:
            w = f32(layer)
            att = cca(_rms_norm(x, w["attn_norm"], eps), w, rotary=rotary, theta=rope_theta)
            x = rescale(x, att, w["attn_scaling"])
            y, aux, pick, weight, r = _experts(_rms_norm(x, w["mlp_norm"], eps), w, r, first_expert, eps)
            x, aux_total = rescale(x, y, w["mlp_scaling"]), aux_total + aux
            picks.append(pick)
            pick_weights.append(weight)
        x = _rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ embed.T, aux_total, jnp.stack(picks), jnp.stack(pick_weights)


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, *, aux_weight: float, **numerics: Any):
    """Cross-entropy of predicting ``tokens[1:]`` plus ``aux_weight`` x the
    auxiliary losses, and the logits ``[S - 1, V]``."""
    logits, aux, _, _ = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)) + aux_weight * aux, logits
