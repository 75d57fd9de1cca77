"""Plain reference: a decoder whose attention is gated power retention of
degree 2, in its QUADRATIC form only.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no state, no feature map, no
chunks, no kernel, and no import from the program.  It follows Brumby-14B-Base
as its sources describe it: Qwen3-14B-Base's block (pre-norm RMSNorm, grouped
queries, an RMSNorm a head on q and k, rotary embeddings, SwiGLU, untied
head) with the softmax attention replaced by power retention ("Scaling Context
Requires Rethinking Attention", arXiv:2507.04239)::

    log g_t = log sigmoid(u_t Wg + gate_bias)      (float32, one a KV head)
    G_t     = sum_{s <= t} log g_s
    A_ij    = exp(G_i - G_j) (q_i . k_j)^2         (j <= i)
    o_i     = sum_j A_ij v_j / sum_j A_ij

The scores of one KV head's query heads are computed a block of query rows at
a time (``query_block``), the MLP a block of its width at a time and the head
a block of the vocabulary at a time, each with its weights converted to
float32 inside the block: at the published widths and 4,608 tokens nothing
larger than the logits themselves is ever held.

Departures from the published model, forced by the program it is the
yardstick of and stated in the configuration file: rotary pairs are the
interleaved ``(2i, 2i+1)`` (a fixed permutation of the columns of ``wq`` and
``wk``, which seeded random weights absorb).

What ``forward`` can be told otherwise (the controls of the serving check:
each must come out not correct): ``degree`` 1, ``gated`` False (``g = 1``),
``normalised`` False (no denominator), ``rotary`` False, ``kv_int8`` True (k
and v rounded to int8 a token a head before they are used).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and per layer
``attn_norm [D]``, ``wq [D, H, K]``, ``wk/wv [D, G, K]``, ``wg [D, G]``,
``q_norm/k_norm [K]``, ``wo [H, K, D]``, ``mlp_norm [D]``, ``w_gate/w_up [D,
F]``, ``w_down [F, D]``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, K]; position s rotates pair (2i, 2i+1) by s * theta^(-2i/K)."""
    s, _, k = x.shape
    freqs = theta ** (-jnp.arange(0, k, 2, dtype=F32) / k)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _int8(x: jax.Array) -> jax.Array:
    """Rounded to 255 levels a row: what an int8 cache of this row would hold."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.where(scale == 0.0, 1.0, scale)) * scale


def _retention(q, k, v, cum, *, degree: int, normalised: bool, query_block: int) -> jax.Array:
    """q [S, H, K], k / v [S, G, K], cum [S, G] (the gate's running log) -> [S, H, K]."""
    s, h, width = q.shape
    g = k.shape[1]
    block = min(query_block, s)
    blocks = -(-s // block)
    pad = blocks * block - s
    rows = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(blocks, block, g, h // g, width)
    at = jnp.pad(cum, ((0, pad), (0, 0))).reshape(blocks, block, g)
    first = jnp.arange(blocks) * block

    def of_head(args):
        q_g, k_g, v_g, cum_g, at_g = args  # [blocks, block, n, K], [S, K], [S, K], [S], [blocks, block]

        def of_rows(rows_args):
            q_b, at_b, start = rows_args  # [block, n, K], [block], ()
            scores = jnp.einsum("ink,jk->nij", q_b, k_g)
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
            decay = jnp.exp(jnp.where(seen, at_b[:, None] - cum_g[None, :], 0.0))
            weights = jnp.where(seen[None], decay[None] * scores ** degree, 0.0)
            out = jnp.einsum("nij,jk->ink", weights, v_g)
            if normalised:
                out = out / jnp.sum(weights, axis=-1).T[..., None]
            return out

        return jax.lax.map(of_rows, (q_g, at_g, first))  # [blocks, block, n, K]

    per_head = (rows.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2), v.transpose(1, 0, 2), cum.T, at.transpose(2, 0, 1))
    out = jax.lax.map(of_head, per_head)  # [G, blocks, block, n, K]
    return out.transpose(1, 2, 0, 3, 4).reshape(blocks * block, h, width)[:s]


def _mlp(h: jax.Array, w_gate, w_up, w_down, block: int) -> jax.Array:
    """SwiGLU, ``block`` columns of its width at a time."""
    width = w_gate.shape[1]
    block = min(block, width)

    def body(i, acc):
        start = jnp.minimum(i * block, width - block)  # the last block may overlap the one before it
        cols = jnp.arange(block) + start
        fresh = (cols >= i * block).astype(F32)  # ... and then adds only the columns not yet added
        gate = h @ jax.lax.dynamic_slice_in_dim(w_gate, start, block, axis=1).astype(F32)
        up = h @ jax.lax.dynamic_slice_in_dim(w_up, start, block, axis=1).astype(F32)
        return acc + (jax.nn.silu(gate) * up * fresh) @ jax.lax.dynamic_slice_in_dim(w_down, start, block, axis=0).astype(F32)

    return jax.lax.fori_loop(0, -(-width // block), body, jnp.zeros((h.shape[0], w_down.shape[1]), F32))


def _logits(x: jax.Array, head, block: int) -> jax.Array:
    """``x @ head``, ``block`` columns of the vocabulary at a time."""
    vocab = head.shape[1]
    block = min(block, vocab)

    def body(i, out):
        start = jnp.minimum(i * block, vocab - block)  # an overlapping last block writes the same values again
        w = jax.lax.dynamic_slice_in_dim(head, start, block, axis=1).astype(F32)
        return jax.lax.dynamic_update_slice_in_dim(out, x @ w, start, axis=1)

    return jax.lax.fori_loop(0, -(-vocab // block), body, jnp.zeros((x.shape[0], vocab), F32))


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, gate_bias: float,
    degree: int = 2, gated: bool = True, normalised: bool = True, rotary: bool = True, kv_int8: bool = False,
    query_block: int = 512, mlp_block: int = 2176, vocab_block: int = 9496,
) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            small = {n: layer[n].astype(F32) for n in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo")}
            u = _rms_norm(x, small["attn_norm"], eps)
            q = _rms_norm(jnp.einsum("sd,dhk->shk", u, small["wq"]), small["q_norm"], eps)
            k = _rms_norm(jnp.einsum("sd,dgk->sgk", u, small["wk"]), small["k_norm"], eps)
            v = jnp.einsum("sd,dgk->sgk", u, small["wv"])
            if rotary:
                q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            if kv_int8:
                k, v = _int8(k), _int8(v)
            log_g = jax.nn.log_sigmoid(u @ small["wg"] + gate_bias) if gated else jnp.zeros((x.shape[0], k.shape[1]), F32)
            att = _retention(q, k, v, jnp.cumsum(log_g, axis=0), degree=degree, normalised=normalised, query_block=query_block)
            x = x + jnp.einsum("shk,hkd->sd", att, small["wo"])
            x = x + _mlp(_rms_norm(x, small["mlp_norm"], eps), layer["w_gate"], layer["w_up"], layer["w_down"], mlp_block)
        x = _rms_norm(x, weights["final_norm"].astype(F32), eps)
        return _logits(x, weights["head"], vocab_block)


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any):
    """Mean cross-entropy of predicting ``tokens[1:]`` from ``tokens[:-1]``,
    and the logits ``[S - 1, V]`` it was taken from."""
    logits = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
