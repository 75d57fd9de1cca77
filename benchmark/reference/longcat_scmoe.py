"""Plain reference: LongCat-Flash's decoder block (the language model of
LongCat-Flash-Omni; LongCat-Flash Technical Report, arXiv:2509.01322): a
DOUBLE layer of two latent-attention sublayers and two dense FFNs round a
shortcut-connected expert branch, whose softmax router has more outputs than
experts: the last ones are identity ("zero-computation") experts.  THIS chip
holds a range of the real experts and a slice of the vocabulary.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, keys
and values expanded a head (not the absorbed form), no kernels, no cache, no
batching, no sorting, no import from the program.  ``norm`` is RMSNorm with
``eps``, one weight vector each.  Written from the equations:

1. The block, on ``x [S, D]``; sublayers 0 and 1 have their own weights::

       a0 = x  + MLA_0(norm(x));    u = norm(a0);    m = Experts(u)
       b0 = a0 + FFN_0(u)
       a1 = b0 + MLA_1(norm(b0))
       b1 = a1 + FFN_1(norm(a1));   block output = b1 + m

   The expert branch reads the FIRST sublayer's post-attention norm and joins
   the stream after the SECOND FFN (the shortcut: in a deployment its exchange
   runs beside MLA_1 and both FFNs).  ``FFN(h) = (silu(h Wg) * (h Wu)) Wd``.
2. ``MLA(h)``: ``c_q = norm_q(h Wq_a) * q_scale``; ``q = c_q Wq_b`` as H heads
   of ``[q_nope N | q_rope P]``.  ``[c | k_r] = h Wkv_a``; ``c = norm_kv(c) *
   kv_scale``; ``k_r`` is rotated and is ONE head shared by all.  ``[k_nope_h N
   | v_h V] = c Wkv_b`` a head.  ``score_h = (q_nope_h . k_nope_h + q_rope_h .
   k_r) * (N + P)^-0.5``; causal softmax; ``o_h = sum p v_h``; output
   ``concat(o_h) Wo``.  The scales are the report's ``(D / rank)^0.5`` (the
   adapter computes them; the published keys are booleans), on the normed latent.
3. Rotary over the P rotary dims, pairs ``(2i, 2i + 1)``, ``inv_i =
   theta^(-2i / P)``, no scaling.
4. ``Experts(u)``: ``s = softmax(u Wr)`` over ALL ``E + Z`` outputs; the
   ``top_k`` largest ``s + beta`` are the picks (``beta`` picks and never
   weighs); ``w_e = scaling * s_e`` for a pick, NOT renormalised; ``m = sum over
   the picked real experts held here (first_expert <= e < first_expert + held)
   of w_e FFN_e(u)  +  sum over the picked e >= E of w_e u``.  A picked real
   expert that is held elsewhere adds nothing, as in the program; the identity
   part is whole (it is computed where the token lives).
5. Embedding, the blocks, ``norm``, an untied head over the slice.

Not here, as not in the program: the audio and vision encoders and the codec
decoder of the Omni model, FP8.

Memory: the check runs beside the program's weights and pool, so leaves are
upcast where they are used; a dense FFN runs as a sum over slices of its hidden
width (``silu(h Wg) * (h Wu)`` is elementwise in that width, so the sum over
slices IS the FFN) and the held experts as a loop, one slice's or expert's
float32 matrices at a time (``jax.lax.scan``); attention runs a block of
queries at a time against all keys.

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]``; a layer:
``sub`` (two of: ``attn_norm [D]``, ``wq_a [D, Rq]``, ``q_norm [Rq]``, ``wq_b
[Rq, H, N + P]``, ``wkv_a [D, C + P]``, ``kv_norm [C]``, ``wkv_b [C, H, N + V]``,
``wo [H, V, D]``, ``ffn_norm [D]``, ``w_gate / w_up [D, F]``, ``w_down [F, D]``),
``router [D, E + Z]``, ``router_bias [E + Z]``, ``e_gate / e_up [held, D, Fe]``,
``e_down [held, Fe, D]``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: queries a block of the attention, and columns a slice of a dense FFN's hidden width
QUERY_BLOCK, FFN_SLICE = 128, 2048


def _f32(a: jax.Array) -> jax.Array:
    return a.astype(jnp.float32)


def _norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, P]; position s turns pair (2i, 2i + 1) by ``s * theta^(-2i / P)``."""
    p = x.shape[-1]
    inv = np.asarray(theta ** (-2.0 * np.arange(p // 2, dtype=np.float64) / p), np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def latent_attention(h: jax.Array, w: Dict[str, jax.Array], *, eps: float, nope: int, latent: int, rope_theta: float,
                     q_scale: float, kv_scale: float) -> jax.Array:
    """Step 2 on the normed input ``h [S, D]`` -> what the sublayer adds ``[S, D]``."""
    c_q = _norm(h @ _f32(w["wq_a"]), w["q_norm"], eps) * q_scale
    q = jnp.einsum("sr,rhk->shk", c_q, _f32(w["wq_b"]))
    kv = h @ _f32(w["wkv_a"])
    c = _norm(kv[:, :latent], w["kv_norm"], eps) * kv_scale
    k_r = _rope(kv[:, None, latent:], rope_theta)[:, 0]                             # [S, P], one for all heads
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], rope_theta)
    expanded = jnp.einsum("sc,chk->shk", c, _f32(w["wkv_b"]))                        # [S, H, N + V]
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    s, scale = h.shape[0], (nope + q_rope.shape[-1]) ** -0.5
    outs = []
    for lo in range(0, s, QUERY_BLOCK):                                              # a block of queries against all keys
        hi = min(lo + QUERY_BLOCK, s)
        scores = (jnp.einsum("shn,thn->hst", q_nope[lo:hi], k_nope) + jnp.einsum("shp,tp->hst", q_rope[lo:hi], k_r)) * scale
        seen = jnp.arange(s)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hst,thv->shv", probs, v))
    return jnp.einsum("shv,hvd->sd", jnp.concatenate(outs, axis=0), _f32(w["wo"]))


def swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def dense_ffn(h: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    """``FFN(h)``, as a sum over slices of its hidden width where that is whole slices."""
    d, f = w["w_gate"].shape
    if f % FFN_SLICE:
        return swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    n = f // FFN_SLICE
    cols = lambda a: a.reshape(d, n, FFN_SLICE).transpose(1, 0, 2)  # noqa: E731
    y, _ = jax.lax.scan(
        lambda y, m: (y + swiglu(h, *m), None), jnp.zeros_like(h),
        (cols(w["w_gate"]), cols(w["w_up"]), w["w_down"].reshape(n, FFN_SLICE, d)),
    )
    return y


def route(u: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scaling: float) -> Tuple[jax.Array, jax.Array]:
    """(the picks ``[S, k]`` among all ``E + Z`` outputs, their weights ``[S, k]``) of step 4."""
    s = jax.nn.softmax(u @ _f32(router), axis=-1)
    _, picks = jax.lax.top_k(s + _f32(bias)[None, :], top_k)
    return picks, scaling * jnp.take_along_axis(s, picks, axis=1)


def experts(u: jax.Array, w: Dict[str, jax.Array], *, top_k: int, scaling: float, real_experts: int, first_expert: int,
            held: int) -> jax.Array:
    """Step 4's ``m``: the held real experts' part, every held expert on every
    token, one after the other, weighted by what the token's picks give it
    (nothing where it was not picked), and the identity experts' part."""
    if w["e_gate"].shape[0] != held or not 0 <= first_expert <= first_expert + held <= real_experts:
        raise ValueError(f"{w['e_gate'].shape[0]} experts' matrices for {held} held from {first_expert} of {real_experts}")
    picks, weights = route(u, w["router"], w["router_bias"], top_k=top_k, scaling=scaling)

    def add_expert(m, expert):
        e, gate, up, down = expert
        mine = jnp.sum(jnp.where(picks == first_expert + e, weights, 0.0), axis=-1)          # [S]
        return m + mine[:, None] * swiglu(u, gate, up, down), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return m + jnp.sum(jnp.where(picks >= real_experts, weights, 0.0), axis=-1)[:, None] * u


def block(x: jax.Array, layer: Dict[str, Any], *, eps: float, mla: Dict[str, Any], moe: Dict[str, Any]) -> jax.Array:
    """Step 1."""
    s0, s1 = layer["sub"]
    a0 = x + latent_attention(_norm(x, s0["attn_norm"], eps), s0, eps=eps, **mla)
    u = _norm(a0, s0["ffn_norm"], eps)
    m = experts(u, layer, **moe)
    b0 = a0 + dense_ffn(u, s0)
    a1 = b0 + latent_attention(_norm(b0, s1["attn_norm"], eps), s1, eps=eps, **mla)
    b1 = a1 + dense_ffn(_norm(a1, s1["ffn_norm"], eps), s1)
    return b1 + m


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, nope: int, latent: int,
    q_scale: float, kv_scale: float, top_k: int, scaling: float, real_experts: int, first_expert: int, held: int,
) -> jax.Array:
    """Logits ``[S, V]`` of one sequence."""
    mla = {"nope": nope, "latent": latent, "rope_theta": rope_theta, "q_scale": q_scale, "kv_scale": kv_scale}
    moe = {"top_k": top_k, "scaling": scaling, "real_experts": real_experts, "first_expert": first_expert, "held": held}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"][tokens])
        for layer in weights["layers"]:
            x = block(x, layer, eps=eps, mla=mla, moe=moe)
        return _norm(x, weights["final_norm"], eps) @ _f32(weights["head"])


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any) -> Tuple[jax.Array, jax.Array]:
    """Mean cross-entropy of predicting ``tokens[1:]`` (no auxiliary term: the
    router balances by its bias) and the logits ``[S - 1, V]``."""
    logits = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
