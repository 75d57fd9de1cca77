"""Plain reference: Falcon-H1's block, attention heads and Mamba-2 heads side
by side under one norm, the Mamba-2 mixer as its RECURRENCE only.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no chunks, no quadratic form of
the scan, no cache, no kernel, and no import from the program.  The block is
the Falcon-H1 report's parallel hybrid (arXiv:2507.22448), the mixer the
published Mamba-2 (Dao and Gu, arXiv:2405.21060), every scalar a key of the
model's ``config.json``::

    x_0 = E[token] * embedding_multiplier
    u   = RMSNorm(x)
    a   = Attention(u * attention_in_multiplier) * attention_out_multiplier
          (k times key_multiplier; rotary on every dim; causal softmax)
    [z | xBC | dt] = ((u * ssm_in_multiplier) W_in) * m       m: ssm_multipliers over z, x, B, C, dt
    xBC_t = silu(sum_i w_i xBC_{t-3+i} + b)                   (depthwise, causal, zeros before the start)
    D_t = softplus(dt_t + dt_bias),  A = -exp(A_log)
    H_t = exp(D_t A) H_{t-1} + D_t x_t B_t^T                  (a head's state, P x N, zero before the start)
    y_t = H_t C_t + Dskip x_t
    y   = RMSNorm(y * silu(z)) over each group's channels, times its weight
    s   = (y W_out) * ssm_out_multiplier
    h   = x + a + s
    x'  = h + ((silu((v W_gate) * mlp_0) * (v W_up)) W_down) * mlp_1,   v = RMSNorm(h)
    logits = (RMSNorm(x) W_head) * lm_head_multiplier

The state is carried one token at a time under ``lax.scan``; the convolution
is four shifted sums; attention runs a KV head and a block of query rows at a
time, the MLP a block of its width at a time and the head a block of the
vocabulary at a time, each with its weights converted to float32 inside the
block, so that at the published widths nothing larger than the logits
themselves is ever held.

Departures from the published model, forced by the program it is the
yardstick of and stated in the configuration file: rotary pairs are the
interleaved ``(2i, 2i+1)`` (a fixed permutation of the columns of ``wq`` and
``wk``, which seeded random weights absorb).

What ``forward`` can be told otherwise (the controls of the serving check:
each must come out not correct): ``skip`` False (``Dskip`` left out),
``norm_groups`` 1 (the gated norm over all channels at once), ``shared_group``
True (B and C of group 0 given to every head), ``key_multiplier`` and
``ssm_out_multiplier`` 1, ``attention`` / ``ssm`` / ``mlp`` False (the branch
left out).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and per layer
``attn_norm [D]``, ``wq [D, H, K]``, ``wk/wv [D, G, K]``, ``wo [H, K, D]``,
``w_in [D, 2 W + 2 g N + h]``, ``conv_w [4, W + 2 g N]``, ``conv_b``,
``dt_bias/A_log/D [h]``, ``ssm_norm [W]``, ``w_out [W, D]``, ``mlp_norm [D]``,
``w_gate/w_up [D, F]``, ``w_down [F, D]``.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

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


def _recurrence(x, b, c, step, a, skip) -> jax.Array:
    """x [S, h, P], b / c [S, h, N] (a head's own group's), step [S, h], a [h],
    skip [h] -> y [S, h, P], one token at a time from an empty state."""

    def token(state, at):
        x_t, b_t, c_t, d_t = at
        state = jnp.exp(d_t * a)[:, None, None] * state + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + skip[:, None] * x_t

    empty = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), F32)
    return jax.lax.scan(token, empty, (x, b, c, step))[1]


def _mamba2(u, w, *, heads, head_dim, d_state, groups, conv, eps, ssm_multipliers: Sequence[float], skip: bool,
            norm_groups: int, shared_group: bool) -> jax.Array:
    """The mixer on the normed and scaled input ``u`` [S, D] -> [S, W] (before the out-projection)."""
    s = u.shape[0]
    width, state = heads * head_dim, groups * d_state
    sizes = (width, width, state, state, heads)
    spans = jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(sizes, ssm_multipliers)])
    proj = (u @ w["w_in"]) * spans
    z, xbc, dt = proj[:, :width], proj[:, width: 2 * width + 2 * state], proj[:, 2 * width + 2 * state:]
    before = jnp.pad(xbc, ((conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w["conv_w"][i] * before[i: i + s] for i in range(conv)) + w["conv_b"])
    x = xbc[:, :width].reshape(s, heads, head_dim)
    b = xbc[:, width: width + state].reshape(s, groups, d_state)
    c = xbc[:, width + state:].reshape(s, groups, d_state)
    of_head = jnp.zeros((heads,), jnp.int32) if shared_group else jnp.arange(heads) // (heads // groups)
    step = jax.nn.softplus(dt + w["dt_bias"])
    y = _recurrence(x, b[:, of_head], c[:, of_head], step, -jnp.exp(w["A_log"]), w["D"] if skip else jnp.zeros_like(w["D"]))
    y = y.reshape(s, width) * jax.nn.silu(z)
    y = y.reshape(s, norm_groups, width // norm_groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(s, width) * w["ssm_norm"]


def _mlp(h: jax.Array, w_gate, w_up, w_down, block: int, gate_multiplier: float) -> jax.Array:
    """SwiGLU, ``block`` columns of its width at a time."""
    width = w_gate.shape[1]
    block = min(block, width)

    def body(i, acc):
        start = jnp.minimum(i * block, width - block)  # the last block may overlap the one before it
        cols = jnp.arange(block) + start
        fresh = (cols >= i * block).astype(F32)  # ... and then adds only the columns not yet added
        gate = (h @ jax.lax.dynamic_slice_in_dim(w_gate, start, block, axis=1).astype(F32)) * gate_multiplier
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


_SMALL = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "w_out")


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, heads: int, head_dim: int,
    d_state: int, groups: int, conv: int, embedding_multiplier: float, key_multiplier: float,
    attention_in_multiplier: float, attention_out_multiplier: float, ssm_in_multiplier: float,
    ssm_multipliers: Sequence[float], ssm_out_multiplier: float, mlp_multipliers: Sequence[float],
    lm_head_multiplier: float, skip: bool = True, norm_groups: int = 0, shared_group: bool = False,
    attention: bool = True, ssm: bool = True, mlp: bool = True,
    query_block: int = 512, mlp_block: int = 2688, vocab_block: int = 8160,
) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32) * embedding_multiplier
        for layer in weights["layers"]:
            w = {n: layer[n].astype(F32) for n in _SMALL}
            u = _rms_norm(x, w["attn_norm"], eps)
            h = x
            if attention:
                ua = u * attention_in_multiplier
                q = _rope(jnp.einsum("sd,dhk->shk", ua, w["wq"]), rope_theta)
                k = _rope(jnp.einsum("sd,dgk->sgk", ua, w["wk"]) * key_multiplier, rope_theta)
                att = _attention(q, k, jnp.einsum("sd,dgk->sgk", ua, w["wv"]), query_block=query_block)
                h = h + jnp.einsum("shk,hkd->sd", att, w["wo"]) * attention_out_multiplier
            if ssm:
                y = _mamba2(
                    u * ssm_in_multiplier, w, heads=heads, head_dim=head_dim, d_state=d_state, groups=groups, conv=conv,
                    eps=eps, ssm_multipliers=ssm_multipliers, skip=skip, norm_groups=norm_groups or groups,
                    shared_group=shared_group,
                )
                h = h + (y @ w["w_out"]) * ssm_out_multiplier
            x = h
            if mlp:
                v = _rms_norm(h, w["mlp_norm"], eps)
                x = h + _mlp(v, layer["w_gate"], layer["w_up"], layer["w_down"], mlp_block, mlp_multipliers[0]) * mlp_multipliers[1]
        x = _rms_norm(x, weights["final_norm"].astype(F32), eps)
        return _logits(x, weights["head"], vocab_block) * lm_head_multiplier


def loss_and_logits(weights: Dict[str, Any], tokens: jax.Array, **numerics: Any):
    """Mean cross-entropy of predicting ``tokens[1:]`` from ``tokens[:-1]``,
    and the logits ``[S - 1, V]`` it was taken from."""
    logits = forward(weights, tokens[:-1], **numerics)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1)), logits
