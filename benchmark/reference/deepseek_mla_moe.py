"""Plain reference: DeepSeek-V3's decoder block, multi-head latent attention
and a sigmoid, group-limited top-k router over many SwiGLU experts beside a
shared expert, after a prefix of dense layers, of which THIS chip holds a
range of the experts and a slice of the vocabulary.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no sorting, no absorbed products, no import from the
program.  RMSNorm with ``eps`` throughout.  For layer ``l`` on ``x [S, D]``,
with ``h = rmsnorm(x)``:

1. Latent attention, as published (DeepSeek-V2, section 2.1; V3 keeps it).
   ``c_q = rmsnorm(h Wq_a) [R_q]``; ``q = c_q Wq_b -> H heads of [q_nope N |
   q_rope P]``.  ``[c_kv C | k_r P] = h Wkv_a``; ``c_kv = rmsnorm(c_kv)``;
   ``k_r = rope(k_r)``, ONE for all heads; ``q_rope = rope(q_rope)``.
   ``[k_nope_h N | v_h V] = c_kv Wkv_b`` a head.  ``score_h = (q_nope_h .
   k_nope_h + q_rope_h . k_r) * softmax_scale``; causal softmax; ``o_h = sum p
   v_h``; ``x += concat(o_h) Wo``.
2. Rotary over the P rotary dims, pairs ``(2i, 2i+1)`` (the published
   inference code's pairing), YaRN's static inverse frequencies:
   ``inv_extra_i = theta^(-2i/P)``, ``inv_inter_i = inv_extra_i / factor``,
   ``corr(n) = P ln(original / (2 pi n)) / (2 ln theta)``, ``low =
   floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` clipped to ``[0,
   P - 1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_i =
   inv_inter_i ramp_i + inv_extra_i (1 - ramp_i)``.  cos and sin carry the
   factor ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``,
   ``mscale(f, m) = 0.1 m ln f + 1`` (1.0 at the published values), and
   ``softmax_scale = (N + P)^-0.5 * mscale(factor, mscale_all_dim)^2``
   (:func:`yarn_scales`).
3. Layers ``l < first_dense``: ``x += Wdown (silu(Wgate h2) * Wup h2)`` with
   ``h2 = rmsnorm(x)``.  After them (DeepSeek-V3, section 2.1.2): ``sc =
   sigmoid(h2 Wr) [E]``; ``sel = sc + b`` (the bias selects, it never weighs);
   ``n_group`` groups of consecutive experts, a group's score the sum of its
   two largest ``sel``; the ``topk_group`` best groups stay; the ``top_k``
   largest ``sel`` inside them are the picks; ``w = sc[picks] / (sum sc[picks]
   + 1e-20) * scaling``; ``x += sum over the picks held here of w_e E_e(h2) +
   E_shared(h2)``, every ``E`` a SwiGLU.  What experts held elsewhere would
   add is left out, as the program leaves it out.
4. After the last layer ``rmsnorm`` and the untied head over the slice.

Not here, as not in the program: the multi-token-prediction module (the main
model's next-token logits are whole without it), FP8 block scaling (weights
arrive as the program holds them, bfloat16, and are upcast).

Memory: the check runs beside the program's weights and pool, so a layer's
leaves are upcast as the layer is reached and the held experts are a loop
(``jax.lax.scan``), one expert's float32 matrices at a time.

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]``; a layer:
``attn_norm [D]``, ``wq_a [D, R_q]``, ``q_norm [R_q]``, ``wq_b [R_q, H, N +
P]``, ``wkv_a [D, C + P]``, ``kv_norm [C]``, ``wkv_b [C, H, N + V]``, ``wo
[H, V, D]``, ``mlp_norm [D]`` and either ``w_gate/w_up [D, F]``, ``w_down
[F, D]`` or ``router [D, E]``, ``router_bias [E]``, ``w_gate/w_up [held, D,
F]``, ``w_down [held, F, D]``, ``shared_w_gate/shared_w_up [D, F_s]``,
``shared_w_down [F_s, D]``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_scales(rope_scaling: Dict[str, Any], qk_head_dim: int) -> Tuple[float, float]:
    """(the factor on cos and sin, the softmax scale) of a published
    ``rope_scaling`` group of type yarn."""
    factor = float(rope_scaling["factor"])
    all_dim = _mscale(factor, float(rope_scaling.get("mscale_all_dim", 0.0)))  # 1.0 where the key is absent or 0
    return _mscale(factor, float(rope_scaling.get("mscale", 1.0))) / all_dim, qk_head_dim ** -0.5 * all_dim * all_dim


def yarn_inv_freq(rope_dim: int, theta: float, rope_scaling: Dict[str, Any]) -> np.ndarray:
    i = np.arange(rope_dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / rope_dim)
    original = float(rope_scaling["original_max_position_embeddings"])

    def corr(rotations: float) -> float:
        return rope_dim * math.log(original / (2.0 * math.pi * rotations)) / (2.0 * math.log(theta))

    low = max(math.floor(corr(float(rope_scaling["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope_scaling["beta_slow"]))), rope_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / float(rope_scaling["factor"]) * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope(x: jax.Array, inv: np.ndarray, factor: float) -> jax.Array:
    """x: [S, heads, P]; position s turns pair (2i, 2i+1) by s * inv[i]."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = factor * jnp.cos(ang)[:, None, :], factor * jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def latent_attention(h: jax.Array, w: Dict[str, jax.Array], *, eps: float, nope: int, latent: int,
                     inv: np.ndarray, on_cos_sin: float, softmax_scale: float) -> jax.Array:
    """Step 1 on the normed input ``h [S, D]`` -> what attention adds ``[S, D]``."""
    c_q = _rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = jnp.einsum("sr,rhk->shk", c_q, w["wq_b"])
    kv = h @ w["wkv_a"]
    c_kv = _rms_norm(kv[:, :latent], w["kv_norm"], eps)
    k_r = _rope(kv[:, None, latent:], inv, on_cos_sin)                       # [S, 1, P]
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv, on_cos_sin)
    expanded = jnp.einsum("sc,chk->shk", c_kv, w["wkv_b"])                    # [S, H, N + V]
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scores = (jnp.einsum("shn,thn->hst", q_nope, k_nope) + jnp.einsum("shp,tp->hst", q_rope, k_r[:, 0])) * softmax_scale
    s = h.shape[0]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("shv,hvd->sd", jnp.einsum("hst,thv->shv", probs, v), w["wo"])


def route(h: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, n_group: int, topk_group: int,
          scaling: float) -> Tuple[jax.Array, jax.Array]:
    """(the picks ``[S, k]``, their weights ``[S, k]``) of step 3."""
    scores = jax.nn.sigmoid(h @ router)
    select = scores + bias[None, :]
    s, e = scores.shape
    in_group = jnp.sort(select.reshape(s, n_group, e // n_group), axis=-1)
    group_score = in_group[..., -1] + in_group[..., -2]
    threshold = jnp.sort(group_score, axis=-1)[:, n_group - topk_group]         # the topk_group-th best
    kept = group_score >= threshold[:, None]
    inside = jnp.where(jnp.repeat(kept, e // n_group, axis=1), select, -jnp.inf)
    _, picks = jax.lax.top_k(inside, top_k)
    top = jnp.take_along_axis(scores, picks, axis=1)
    return picks, top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling


def swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed_part(h: jax.Array, w: Dict[str, jax.Array], picks: jax.Array, weights: jax.Array, first: int) -> jax.Array:
    """What the experts ``first .. first + held - 1`` add: every held expert
    on every token, one after the other, weighted by what the token's picks
    give it (nothing where it was not picked): plain, not fast."""

    def add_expert(y, expert):
        e, gate, up, down = expert
        mine = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)          # [S]
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 (one expert's matrices at a time)
        return y + mine[:, None] * swiglu(h, f32(gate), f32(up), f32(down)), None

    held = w["w_gate"].shape[0]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return y


def expert_layer(h: jax.Array, w: Dict[str, jax.Array], *, first: int, shared: bool = True, **router: Any) -> jax.Array:
    """Step 3's expert layer on the normed input ``h [S, D]``; ``w`` float32
    but for the three stacks of held experts, upcast an expert at a time."""
    picks, weights = route(h, w["router"], w["router_bias"], **router)
    y = routed_part(h, w, picks, weights, first)
    return y + swiglu(h, w["shared_w_gate"], w["shared_w_up"], w["shared_w_down"]) if shared else y


_STACKS = ("w_gate", "w_up", "w_down")


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, rope_scaling: Dict[str, Any],
    nope: int, rope_dim: int, latent: int, top_k: int, n_group: int, topk_group: int, scaling: float,
    first_expert: int,
) -> jax.Array:
    """Logits ``[S, V]`` of one sequence."""
    inv = yarn_inv_freq(rope_dim, rope_theta, rope_scaling)
    on_cos_sin, softmax_scale = yarn_scales(rope_scaling, nope + rope_dim)
    router = {"top_k": top_k, "n_group": n_group, "topk_group": topk_group, "scaling": scaling}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"]:
            sparse = "router" in layer
            w = {k: v if sparse and k in _STACKS else v.astype(jnp.float32) for k, v in layer.items()}
            x = x + latent_attention(
                _rms_norm(x, w["attn_norm"], eps), w, eps=eps, nope=nope, latent=latent, inv=inv,
                on_cos_sin=on_cos_sin, softmax_scale=softmax_scale,
            )
            h = _rms_norm(x, w["mlp_norm"], eps)
            x = x + (expert_layer(h, w, first=first_expert, **router) if sparse else swiglu(h, w["w_gate"], w["w_up"], w["w_down"]))
        x = _rms_norm(x, weights["final_norm"].astype(jnp.float32), eps)
        return x @ weights["head"].astype(jnp.float32)


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any) -> Tuple[jax.Array, jax.Array]:
    """Mean cross-entropy of predicting ``tokens[1:]`` (no auxiliary term:
    the router balances by its bias) and the logits ``[S - 1, V]``."""
    logits = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
