"""Plain reference: Qwen3-Next's block, three Gated-DeltaNet layers to every
gated full-attention layer, experts with a gated shared expert in every layer;
the delta rule as its RECURRENCE only.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no chunks, no WY form, no
triangular solve, no cache, no kernel, and no import from the program.  The
layers are those of the published modelling code (``modeling_qwen3_next.py``;
the linear layer is Gated DeltaNet's, Yang et al., arXiv:2412.06464), every
size a key of the model's ``config.json``::

    norm(x) = x / sqrt(mean(x^2) + eps) * n            n: the leaf as held, ``1 + w`` of the published weight
    x <- x + Mixer_i(norm(x));  x <- x + Experts_i(norm(x));  logits = norm(x) W_head

    Gated DeltaNet (h the normed input):
    [q | k | v | z] = h W_in,  [b | a] = h W_ba
    [q | k | v]_t = silu(sum_i w_i [q | k | v]_{t-3+i})          (depthwise, causal, no bias, zeros before the start)
    beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)     (a value head each)
    q <- q / sqrt(sum q^2 + 1e-6) * K^-0.5,  k <- k / sqrt(sum k^2 + 1e-6)   (a key head serves its consecutive value heads)
    S <- exp(g_t) S;  r = S^T k_t;  S <- S + k_t (x) beta_t (v_t - r);  o_t = S^T q_t     (S [K, V], zero before the start)
    y = m * (o / sqrt(mean(o^2) + eps)) * silu(z)                 (over a head's V values; the norm BEFORE the gate)
    out = y W_out

    Gated attention:
    [query | gate] a head = h W_q;  k, v = h W_k, h W_v
    q <- rope(norm_K(query)),  k <- rope(norm_K(k))               (rotary on the first ``rotary_dim`` of a head)
    out = (softmax(q k^T K^-0.5, causal) v * sigmoid(gate)) W_o   (a group of query heads a KV head)

    Experts:
    p = softmax(h W_r) over all outputs;  the top_k largest are the picks, w = p[picks] / sum
    out = sum over the picks that land on a held expert e of w_e W_down,e (silu(W_gate,e h) * W_up,e h)
          + sigmoid(h . w_g) * W_down,s (silu(W_gate,s h) * W_up,s h)

The state is carried one token at a time under ``lax.scan``; the convolution
is four shifted sums; attention runs a KV head and a block of query rows at a
time, the held experts one after the other over all tokens (each with its
weights converted to float32 inside its step) and the head a block of the
vocabulary at a time, so that at the published widths nothing larger than the
logits themselves is ever held.

Departures from the published model, forced by the program it is the
yardstick of and stated in the configuration file: rotary pairs are the
interleaved ``(2i, 2i+1)`` of a head's first ``rotary_dim`` values (upstream:
``(i, i + rotary_dim / 2)``; a fixed permutation of columns of ``wq`` and
``wk``, which seeded random weights absorb); the columns of ``w_in`` are ``[q | k
| v | z]`` and of ``w_ba`` ``[b | a]``, each segment head after head (upstream
orders both key head by key head: a fixed permutation again); a norm's leaf is
``1 + w`` of the published zero-centred weight (a converter's ``+ 1``); the
experts outside the held range add nothing.

What ``forward`` can be told otherwise (the controls of the serving check: each
must come out not correct): ``correct`` False (the update writes ``beta v``
without ``- r``: a gated linear attention), ``beta_one`` (``beta`` of 1),
``output_gate`` False, ``shared_gate`` False, ``gate_before_norm`` (the gated
norm as Mamba-2's: ``norm(o * silu(z))``), ``rotary_all`` (rotary on the whole
head), ``state_dtype`` (the state rounded to it after every token).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and per layer
``mixer_norm [D]``, ``ffn_norm [D]``, ``router [D, E]``, ``w_gate / w_up [held, D,
F]``, ``w_down [held, F, D]``, ``shared_w_gate / shared_w_up [D, Fs]``,
``shared_w_down [Fs, D]``, ``shared_gate [D]``; a linear layer ``w_in [D, 2 Kw + 2
Vw]``, ``w_ba [D, 2 Hv]``, ``conv_w [taps, 2 Kw + Vw]``, ``dt_bias / A_log [Hv]``,
``gdn_norm [V]``, ``w_out [Vw, D]``; a full layer ``wq [D, H, 2 K]``, ``wk / wv [D,
G, K]``, ``wo [H, K, D]``, ``q_norm / k_norm [K]``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float, turned: int) -> jax.Array:
    """x: [S, heads, K]; position s rotates pair (2i, 2i+1), i < turned / 2, by s * theta^(-2i/turned)."""
    s = x.shape[0]
    freqs = theta ** (-jnp.arange(0, turned, 2, dtype=F32) / turned)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    part = x[..., :turned]
    even, odd = part[..., 0::2], part[..., 1::2]
    part = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(part.shape)
    return jnp.concatenate([part, x[..., turned:]], axis=-1)


def _attention(q, k, v, *, query_block: int) -> jax.Array:
    """Causal softmax attention; q [S, H, K], k / v [S, G, K] -> [S, H, K]:
    query head h reads KV head h // (H / G)."""
    s, h, width = q.shape
    g = k.shape[1]
    block = min(query_block, s)
    blocks = -(-s // block)
    rows = jnp.pad(q, ((0, blocks * block - s), (0, 0), (0, 0))).reshape(blocks, block, g, h // g, width)
    first = jnp.arange(blocks) * block

    def of_head(args):
        q_g, k_g, v_g = args  # [blocks, block, n, K], [S, K], [S, K]

        def of_rows(rows_args):
            q_b, start = rows_args
            scores = jnp.einsum("ink,jk->nij", q_b, k_g) * width ** -0.5
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("nij,jk->ink", probs, v_g)

        return jax.lax.map(of_rows, (q_g, first))

    out = jax.lax.map(of_head, (rows.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 2, 0, 3, 4).reshape(blocks * block, h, width)[:s]  # [G, blocks, block, n, K] ->


def _delta_rule(q, k, v, g, beta, *, correct: bool, state_dtype: Any) -> jax.Array:
    """q / k [S, h, K], v [S, h, V], g / beta [S, h] -> o [S, h, V], one token
    at a time from an empty state [h, K, V]."""

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        held = jnp.einsum("hkv,hk->hv", state, k_t) if correct else jnp.zeros_like(v_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - held))[:, None, :]
        state = state.astype(state_dtype).astype(F32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    empty = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, empty, (q, k, v, g, beta))[1]


def _gated_delta_net(h, w, *, heads, key_heads, key_dim, value_dim, conv, eps, correct, beta_one, gate_before_norm,
                     state_dtype) -> jax.Array:
    """The mixer on the normed input ``h`` [S, D] -> [S, D]."""
    s = h.shape[0]
    kw, vw = key_heads * key_dim, heads * value_dim
    proj, ba = h @ w["w_in"], h @ w["w_ba"]
    qkv, z = proj[:, : 2 * kw + vw], proj[:, 2 * kw + vw:].reshape(s, heads, value_dim)
    before = jnp.pad(qkv, ((conv - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w["conv_w"][i] * before[i: i + s] for i in range(conv)))
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(qkv[:, :kw].reshape(s, key_heads, key_dim)) * key_dim ** -0.5
    k = unit(qkv[:, kw: 2 * kw].reshape(s, key_heads, key_dim))
    v = qkv[:, 2 * kw:].reshape(s, heads, value_dim)
    of_head = jnp.arange(heads) // (heads // key_heads)  # value head j reads key head j // (heads / key_heads)
    beta = jnp.ones((s, heads), F32) if beta_one else jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, heads:] + w["dt_bias"])
    o = _delta_rule(q[:, of_head], k[:, of_head], v, g, beta, correct=correct, state_dtype=state_dtype)
    rms = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)  # noqa: E731
    y = rms(o * jax.nn.silu(z)) * w["gdn_norm"] if gate_before_norm else rms(o) * w["gdn_norm"] * jax.nn.silu(z)
    return y.reshape(s, vw) @ w["w_out"]


def _gated_attention(h, w, *, eps, rope_theta, rotary_dim, output_gate, query_block) -> jax.Array:
    """The mixer on the normed input ``h`` [S, D] -> [S, D]."""
    width = w["wk"].shape[-1]
    both = jnp.einsum("sd,dhk->shk", h, w["wq"])
    q, gate = both[..., :width], both[..., width:]
    k, v = jnp.einsum("sd,dgk->sgk", h, w["wk"]), jnp.einsum("sd,dgk->sgk", h, w["wv"])
    q = _rope(_norm(q, w["q_norm"], eps), rope_theta, rotary_dim)
    k = _rope(_norm(k, w["k_norm"], eps), rope_theta, rotary_dim)
    att = _attention(q, k, v, query_block=query_block)
    if output_gate:
        att = att * jax.nn.sigmoid(gate)
    return jnp.einsum("shk,hkd->sd", att, w["wo"])


def _experts(h, layer, *, top_k: int, first_expert: int, shared_gate: bool) -> jax.Array:
    """The held experts one after the other over all tokens, and the shared expert under its gate."""
    probs = jax.nn.softmax(h @ layer["router"].astype(F32), axis=-1)
    top_p, picks = jax.lax.top_k(probs, top_k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    held = layer["w_up"].shape[0]

    def expert(e, acc):
        w_e = jnp.sum(jnp.where(picks == first_expert + e, weights, 0.0), axis=-1, keepdims=True)  # [S, 1]; 0: not picked
        gate, up, down = (layer[n][e].astype(F32) for n in ("w_gate", "w_up", "w_down"))
        return acc + w_e * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    out = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(h))
    shared = (jax.nn.silu(h @ layer["shared_w_gate"].astype(F32)) * (h @ layer["shared_w_up"].astype(F32))) @ layer["shared_w_down"].astype(F32)
    if shared_gate:
        shared = shared * jax.nn.sigmoid(h @ layer["shared_gate"].astype(F32))[:, None]
    return out + shared


def _logits(x: jax.Array, head, block: int) -> jax.Array:
    """``x @ head``, ``block`` columns of the vocabulary at a time."""
    vocab = head.shape[1]
    block = min(block, vocab)

    def body(i, out):
        start = jnp.minimum(i * block, vocab - block)  # an overlapping last block writes the same values again
        w = jax.lax.dynamic_slice_in_dim(head, start, block, axis=1).astype(F32)
        return jax.lax.dynamic_update_slice_in_dim(out, x @ w, start, axis=1)

    return jax.lax.fori_loop(0, -(-vocab // block), body, jnp.zeros((x.shape[0], vocab), F32))


_LINEAR = ("w_in", "w_ba", "conv_w", "dt_bias", "A_log", "gdn_norm", "w_out")
_FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, rotary_dim: int, heads: int,
    key_heads: int, key_dim: int, value_dim: int, conv: int, top_k: int, first_expert: int,
    correct: bool = True, beta_one: bool = False, output_gate: bool = True, shared_gate: bool = True,
    gate_before_norm: bool = False, rotary_all: bool = False, state_dtype: Any = F32,
    query_block: int = 512, vocab_block: int = 4748,
) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``; a
    layer is linear where it holds ``w_in`` and full attention where it holds ``wq``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            h = _norm(x, layer["mixer_norm"].astype(F32), eps)
            if "w_in" in layer:
                x = x + _gated_delta_net(
                    h, {n: layer[n].astype(F32) for n in _LINEAR}, heads=heads, key_heads=key_heads, key_dim=key_dim,
                    value_dim=value_dim, conv=conv, eps=eps, correct=correct, beta_one=beta_one,
                    gate_before_norm=gate_before_norm, state_dtype=state_dtype,
                )
            else:
                w = {n: layer[n].astype(F32) for n in _FULL}
                turned = w["wk"].shape[-1] if rotary_all else rotary_dim
                x = x + _gated_attention(
                    h, w, eps=eps, rope_theta=rope_theta, rotary_dim=turned, output_gate=output_gate, query_block=query_block
                )
            x = x + _experts(_norm(x, layer["ffn_norm"].astype(F32), eps), layer, top_k=top_k, first_expert=first_expert, shared_gate=shared_gate)
        x = _norm(x, weights["final_norm"].astype(F32), eps)
        return _logits(x, weights["head"], vocab_block)
