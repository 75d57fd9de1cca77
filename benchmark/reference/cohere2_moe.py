"""Plain reference: Command A+'s decoder (``cohere2_moe``), a parallel block
under one LayerNorm, three sliding-window layers with rotary positions to one
full layer without positions, a plain sigmoid top-k router over many SwiGLU
experts beside shared experts that are averaged, and a tied head, of which
THIS chip holds a range of the experts and a slice of the vocabulary.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no ring, no sorting, no import from the program.  For layer
``l`` on ``x [S, D]``:

1. ``h = LN(x) = (x - mean(x)) * rsqrt(var(x) + eps) * g``, mean and variance
   over the ``D`` features, no bias.  ONE norm a layer: attention and the
   experts both read ``h``, and ``x <- x + Attn(h) + FFN(h)``.
2. ``q = h Wq`` (H heads of ``d``), ``k = h Wk``, ``v = h Wv`` (G heads of
   ``d``; query head ``n`` reads KV head ``n // (H / G)``), no bias, no q/k
   norm.  A ``sliding_attention`` layer turns q and k by rotary embeddings
   over all ``d`` dims, pairs ``(2i, 2i+1)``, ``angle = position *
   theta^(-2i/d)``, and position ``i`` sees keys ``j`` with ``i - window < j
   <= i``.  A ``full_attention`` layer has NO positional encoding (q and k as
   projected) and is causal over the whole context.  Scale ``d^-0.5``, softmax
   in float32, ``Attn = concat(o_n) Wo``.
3. ``s = sigmoid(h Wr) [E]``; the picks are the ``top_k`` largest ``s``; ``w =
   s[picks] / sum s[picks]``; ``routed = sum over the picks held here of w_e
   E_e(h)``; ``shared = (1 / n) sum_j S_j(h)`` over the ``n`` shared experts
   (the MEAN: how "average" is read); ``FFN = routed + shared``; every ``E``,
   ``S`` a SwiGLU ``Wdown (silu(Wgate h) * Wup h)``.  What experts held
   elsewhere would add is left out, as the program leaves it out.
4. After the last layer ``LN`` and ``logits = x E^T * logit_scale`` over the
   slice, ``E`` the embedding.

Not here, as not in the program: the vision tower.

Memory: the check runs beside the program's 9.5 GB of weights and 3.3 GB of
cache, at 4,608 tokens.  So a matrix is upcast where it is used, the held and
the shared experts are loops (``jax.lax.scan``: one expert's float32 matrices
at a time), and attention folds over blocks of queries (``jax.lax.map``): a
``[H, S, S]`` float32 score array would be 10.9 GB, a block's is ``H x
query_block x S``.

Weights: ``embed [V, D]``, ``final_norm [D]``; a layer: ``norm [D]``, ``wq [D,
H, d]``, ``wk / wv [D, G, d]``, ``wo [H, d, D]``, ``router [D, E]``, ``w_gate
/ w_up [held, D, F]``, ``w_down [held, F, D]``, ``shared_w_gate / shared_w_up
[D, n F]``, ``shared_w_down [n F, D]`` (shared expert ``j`` owns the columns,
and rows, ``[j F, (j + 1) F)``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"


def _f32(a: jax.Array) -> jax.Array:
    return a.astype(jnp.float32)


def layer_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * scale


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, d]; position s turns pair (2i, 2i+1) by s * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def project(h: jax.Array, w: Dict[str, jax.Array], sliding: bool, theta: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(q [S, H, d], k [S, G, d], v [S, G, d]) of step 2, rotated on a sliding layer alone."""
    q = jnp.einsum("sd,dhk->shk", h, _f32(w["wq"]))
    k = jnp.einsum("sd,dgk->sgk", h, _f32(w["wk"]))
    v = jnp.einsum("sd,dgk->sgk", h, _f32(w["wv"]))
    return (rope(q, theta), rope(k, theta), v) if sliding else (q, k, v)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, window: Any, query_block: int) -> jax.Array:
    """Causal softmax attention ``[S, H, d]``, a block of queries at a time;
    ``window`` (None: the whole context) as step 2 counts it."""
    s, heads, d = q.shape
    groups = k.shape[1]
    block = min(query_block, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, groups, heads // groups, d)
    starts = jnp.arange(qb.shape[0]) * block
    k_pos = jnp.arange(s)

    def one(args):
        q_blk, start = args
        q_pos = start + jnp.arange(block)
        scores = jnp.einsum("qgrd,kgd->grqk", q_blk, k) * d ** -0.5
        seen = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs, v)

    out = jax.lax.map(one, (qb, starts))
    return out.reshape(-1, heads, d)[:s]


def attention(h: jax.Array, w: Dict[str, jax.Array], *, sliding: bool, theta: float, window: int, query_block: int) -> jax.Array:
    """Step 2 on the normed input ``h [S, D]`` -> what attention adds ``[S, D]``."""
    q, k, v = project(h, w, sliding, theta)
    out = attend(q, k, v, window if sliding else None, query_block)
    return jnp.einsum("shk,hkd->sd", out, _f32(w["wo"]))


def route(h: jax.Array, router: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """(the picks ``[S, k]``, their weights ``[S, k]``) of step 3."""
    top, picks = jax.lax.top_k(jax.nn.sigmoid(h @ router), top_k)
    return picks, top / jnp.sum(top, axis=-1, keepdims=True)


def swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed_part(h: jax.Array, w: Dict[str, jax.Array], picks: jax.Array, weights: jax.Array, first: int) -> jax.Array:
    """What the experts ``first .. first + held - 1`` add: every held expert
    on every token, one after the other, weighted by what the token's picks
    give it (nothing where it was not picked): plain, not fast."""

    def add_expert(y, expert):
        e, gate, up, down = expert
        mine = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)          # [S]
        return y + mine[:, None] * swiglu(h, _f32(gate), _f32(up), _f32(down)), None

    held = w["w_gate"].shape[0]
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return y


def shared_part(h: jax.Array, w: Dict[str, jax.Array], shared: int) -> jax.Array:
    """The MEAN of the ``shared`` shared experts' outputs, an expert at a time."""
    width = w["shared_w_gate"].shape[1] // shared

    def add_expert(y, j):
        cols = lambda a: _f32(jax.lax.dynamic_slice_in_dim(a, j * width, width, axis=1))  # noqa: E731
        down = _f32(jax.lax.dynamic_slice_in_dim(w["shared_w_down"], j * width, width, axis=0))
        return y + swiglu(h, cols(w["shared_w_gate"]), cols(w["shared_w_up"]), down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), jnp.arange(shared))
    return y / shared


def expert_layer(h: jax.Array, w: Dict[str, jax.Array], *, top_k: int, shared: int, first: int, with_shared: bool = True) -> jax.Array:
    """Step 3 on the normed input ``h [S, D]``."""
    picks, weights = route(h, _f32(w["router"]), top_k)
    y = routed_part(h, w, picks, weights, first)
    return y + shared_part(h, w, shared) if with_shared else y


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, window: int,
    layer_types: Sequence[str], top_k: int, shared: int, first_expert: int, logit_scale: float,
    query_block: int = 128,
) -> jax.Array:
    """Logits ``[S, V]`` of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"][tokens])
        for w, kind in zip(weights["layers"], layer_types):
            h = layer_norm(x, _f32(w["norm"]), eps)
            att = attention(h, w, sliding=kind == SLIDING, theta=rope_theta, window=window, query_block=query_block)
            x = x + att + expert_layer(h, w, top_k=top_k, shared=shared, first=first_expert)
        x = layer_norm(x, _f32(weights["final_norm"]), eps)
        return (x @ _f32(weights["embed"]).T) * logit_scale


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any) -> Tuple[jax.Array, jax.Array]:
    """Mean cross-entropy of predicting ``tokens[1:]`` (no auxiliary term)
    and the logits ``[S - 1, V]``."""
    logits = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
