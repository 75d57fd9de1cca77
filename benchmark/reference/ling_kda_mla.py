"""Plain reference: Ling-3.0's block, five Kimi-Delta-Attention layers to every
latent-attention layer, a dense SwiGLU in the leading layers and routed experts
with one shared expert in the rest; the delta rule as its RECURRENCE only,
latent attention UN-absorbed.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no chunks, no WY form, no
two-sided scaling, no triangular solve, no cache, no absorbed product, no
kernel, and no import from the program.  The mixers are published mechanisms
(Kimi Delta Attention: "Kimi Linear", arXiv:2510.26692 section 3, the gated
delta rule with a decay a CHANNEL, here under the published kernels' bounded
gate; multi-head latent attention: DeepSeek-V2, arXiv:2405.04434 section 2.1,
without a query latent; the router: DeepSeek-V3's), every size a key of the
model's ``config.json``::

    norm(x) = x / sqrt(mean(x^2) + eps) * n
    x <- x + Mixer_i(norm(x));  x <- x + FFN_i(norm(x));  logits = norm(x) W_head

    Kimi Delta Attention (h the normed input, 32 heads of K = V = 128):
    [q | k | v | z] = h W_in,  b = h W_b,  a = h W_f + dt_bias            (W_f a FULL projection: a value a channel)
    [q | k | v]_t = silu(sum_i w_i [q | k | v]_{t-3+i})                    (depthwise, causal, no bias, zeros before the start)
    q <- q / sqrt(sum q^2 + 1e-6) * K^-0.5,  k <- k / sqrt(sum k^2 + 1e-6)  (a head each)
    beta = sigmoid(b),  g = lower_bound * sigmoid(exp(A_log_h) a)           (g [heads, K] in (lower_bound, 0))
    S <- Diag(exp(g_t)) S;  r = S^T k_t;  S <- S + k_t (x) beta_t (v_t - r);  o_t = S^T q_t   (S [K, V], zero before the start)
    y = m * (o / sqrt(mean(o^2) + eps)) * sigmoid(z)                       (over a head's V values, ONE weight of V)
    out = y W_out

    Latent attention (no query latent):
    q_h = (h W_q)_h = [nope | rope];  [c | k_r] = h W_kva;  c <- norm(c);  k_r <- rope(k_r)   (ONE k_r for all heads)
    [k_nope_h | v_h] = (c W_kvb)_h                                         (keys and values up-projected for EVERY token)
    scores = (q_nope . k_nope + rope(q_rope) . k_r) * (nope + rope)^-0.5, causal softmax
    out = [(softmax . v)_h * sigmoid(h W_hg)_h] W_o                        (ONE gate a head)

    Experts (DeepSeek-V3's router):
    s = sigmoid(h W_r);  picks: on s + bias, a group's score the sum of its two best, the topk_group best groups kept,
    the top_k best inside them;  w = s[picks] / sum * scaling  (the UNBIASED scores)
    out = sum over the picks that land on a held expert e of w_e W_down,e (silu(W_gate,e h) * W_up,e h)
          + W_down,s (silu(W_gate,s h) * W_up,s h)

The state is carried one token at a time under ``lax.scan``; the convolution
is four shifted sums; attention runs a block of query rows at a time, the held
experts one after the other over all tokens (each with its weights converted
to float32 inside its step) and the head a block of the vocabulary at a time,
so that at the published widths nothing larger than the logits themselves is
ever held.

Departures from the published model, forced by the program it is the
yardstick of and stated in the configuration file: rotary pairs are the
interleaved ``(2i, 2i+1)``; the columns of ``w_in`` are ``[q | k | v | z]``, each
segment head after head; a norm's leaf is the multiplier itself; the experts
outside the held range add nothing.

What ``forward`` can be told otherwise (the controls of the serving check: each
must come out not correct): ``correct`` False (the update writes ``beta v``
without ``- r``), ``beta_one``, ``output_gate`` False (the KDA mixer's),
``head_gate`` False (the latent layer's), ``head_decay`` (every channel of a
head decays by the head's MEAN logarithm: a Gated DeltaNet), ``softplus_gate``
(Kimi Linear's ``g = -exp(A_log) softplus(a)`` in the bounded gate's place),
``state_dtype`` (the state rounded to it after every token).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and per layer
``mixer_norm [D]``, ``ffn_norm [D]``; a dense layer ``w_gate / w_up [D, F]``,
``w_down [F, D]``; an expert layer ``router [D, E]``, ``router_bias [E]``, ``w_gate
/ w_up [held, D, Fe]``, ``w_down [held, Fe, D]``, ``shared_w_gate / shared_w_up [D,
Fs]``, ``shared_w_down [Fs, D]``; a KDA layer ``w_in [D, 4 H K]``, ``w_b [D, H]``,
``w_decay [D, H K]``, ``conv_w [taps, 3 H K]``, ``dt_bias [H K]``, ``A_log [H]``,
``kda_norm [V]``, ``w_out [H V, D]``; a latent layer ``wq [D, H, nope + rope]``,
``wkv_a [D, latent + rope]``, ``kv_norm [latent]``, ``wkv_b [latent, H, nope + v]``,
``w_head_gate [D, H]``, ``wo [H, v, D]``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, heads, P]; position s rotates pair (2i, 2i+1) by s * theta^(-2i/P)."""
    s, _, width = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * (theta ** (-jnp.arange(0, width, 2, dtype=F32) / width))[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _delta_rule(q, k, v, g, beta, *, correct: bool = True, state_dtype: Any = F32) -> jax.Array:
    """q / k [S, h, K], v [S, h, V], g [S, h, K] (a decay a channel), beta [S, h]
    -> o [S, h, V], one token at a time from an empty state [h, K, V]."""

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, :, None] * state
        held = jnp.einsum("hkv,hk->hv", state, k_t) if correct else jnp.zeros_like(v_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - held))[:, None, :]
        state = state.astype(state_dtype).astype(F32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    empty = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, empty, (q, k, v, g, beta))[1]


def _kda(h, w, *, heads, key_dim, conv, eps, lower_bound, correct, beta_one, output_gate, head_decay, softplus_gate,
         state_dtype) -> jax.Array:
    """The Kimi-Delta-Attention mixer on the normed input ``h`` [S, D] -> [S, D]."""
    s, width = h.shape[0], heads * key_dim
    proj = h @ w["w_in"]
    qkv, z = proj[:, : 3 * width], proj[:, 3 * width:].reshape(s, heads, key_dim)
    before = jnp.pad(qkv, ((conv - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w["conv_w"][i] * before[i: i + s] for i in range(conv)))
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(qkv[:, :width].reshape(s, heads, key_dim)) * key_dim ** -0.5
    k = unit(qkv[:, width: 2 * width].reshape(s, heads, key_dim))
    v = qkv[:, 2 * width:].reshape(s, heads, key_dim)
    beta = jnp.ones((s, heads), F32) if beta_one else jax.nn.sigmoid(h @ w["w_b"])
    a = (h @ w["w_decay"] + w["dt_bias"]).reshape(s, heads, key_dim)
    rate = jnp.exp(w["A_log"])[None, :, None]
    g = -rate * jax.nn.softplus(a) if softplus_gate else lower_bound * jax.nn.sigmoid(rate * a)
    if head_decay:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    o = _delta_rule(q, k, v, g, beta, correct=correct, state_dtype=state_dtype)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["kda_norm"]
    if output_gate:
        y = y * jax.nn.sigmoid(z)
    return y.reshape(s, width) @ w["w_out"]


def _latent_attention(h, w, *, eps, nope, latent, rope_theta, head_gate, query_block) -> jax.Array:
    """Latent attention on the normed input ``h`` [S, D] -> [S, D], keys and values expanded a head for every token."""
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    kv = h @ w["wkv_a"]
    c = _norm(kv[:, :latent], w["kv_norm"], eps)
    k_r = _rope(kv[:, None, latent:], rope_theta)[:, 0]                        # [S, P]: one for all heads
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], rope_theta)
    expanded = jnp.einsum("sc,chk->shk", c, w["wkv_b"])                        # [S, H, nope + v]
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scale = (nope + k_r.shape[-1]) ** -0.5
    block = min(query_block, s)
    blocks = -(-s // block)
    pad = lambda t: jnp.pad(t, ((0, blocks * block - s),) + ((0, 0),) * (t.ndim - 1)).reshape((blocks, block) + t.shape[1:])  # noqa: E731

    def of_rows(args):
        qn, qr, start = args
        scores = (jnp.einsum("ihn,thn->hit", qn, k_nope) + jnp.einsum("ihp,tp->hit", qr, k_r)) * scale
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hit,thv->ihv", probs, v)

    att = jax.lax.map(of_rows, (pad(q_nope), pad(q_rope), jnp.arange(blocks) * block)).reshape(blocks * block, *v.shape[1:])[:s]
    if head_gate:
        att = att * jax.nn.sigmoid(h @ w["w_head_gate"])[..., None]
    return jnp.einsum("shv,hvd->sd", att, w["wo"])


def route(h, router, bias, *, top_k: int, n_group: int, topk_group: int, scaling: float):
    """(the picks ``[S, k]``, their weights ``[S, k]``): DeepSeek-V3's router."""
    scores = jax.nn.sigmoid(h @ router)
    select = scores + bias[None, :]
    s, e = scores.shape
    in_group = jnp.sort(select.reshape(s, n_group, e // n_group), axis=-1)
    group_score = in_group[..., -1] + in_group[..., -2]
    threshold = jnp.sort(group_score, axis=-1)[:, n_group - topk_group]       # the topk_group-th best
    kept = group_score >= threshold[:, None]
    inside = jnp.where(jnp.repeat(kept, e // n_group, axis=1), select, -jnp.inf)
    _, picks = jax.lax.top_k(inside, top_k)
    top = jnp.take_along_axis(scores, picks, axis=1)
    return picks, top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts(h, layer, *, first_expert: int, shared: bool = True, **router: Any) -> jax.Array:
    """The held experts one after the other over all tokens, and the shared expert."""
    picks, weights = route(h, layer["router"].astype(F32), layer["router_bias"].astype(F32), **router)

    def expert(e, acc):
        w_e = jnp.sum(jnp.where(picks == first_expert + e, weights, 0.0), axis=-1, keepdims=True)  # [S, 1]; 0: not picked
        return acc + w_e * _swiglu(h, *(layer[n][e].astype(F32) for n in ("w_gate", "w_up", "w_down")))

    out = jax.lax.fori_loop(0, layer["w_up"].shape[0], expert, jnp.zeros_like(h))
    if shared:
        out = out + _swiglu(h, *(layer[n].astype(F32) for n in ("shared_w_gate", "shared_w_up", "shared_w_down")))
    return out


def _logits(x: jax.Array, head, block: int) -> jax.Array:
    """``x @ head``, ``block`` columns of the vocabulary at a time."""
    vocab = head.shape[1]
    block = min(block, vocab)

    def body(i, out):
        start = jnp.minimum(i * block, vocab - block)  # an overlapping last block writes the same values again
        w = jax.lax.dynamic_slice_in_dim(head, start, block, axis=1).astype(F32)
        return jax.lax.dynamic_update_slice_in_dim(out, x @ w, start, axis=1)

    return jax.lax.fori_loop(0, -(-vocab // block), body, jnp.zeros((x.shape[0], vocab), F32))


_KDA = ("w_in", "w_b", "w_decay", "conv_w", "dt_bias", "A_log", "kda_norm", "w_out")
_LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "w_head_gate", "wo")


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, rope_theta: float, heads: int, key_dim: int, conv: int,
    lower_bound: float, nope: int, latent: int, top_k: int, n_group: int, topk_group: int, scaling: float, first_expert: int,
    correct: bool = True, beta_one: bool = False, output_gate: bool = True, head_gate: bool = True, head_decay: bool = False,
    softplus_gate: bool = False, state_dtype: Any = F32, query_block: int = 512, vocab_block: int = 4912,
) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``; a
    layer is Kimi Delta Attention where it holds ``w_in`` and latent attention
    where it holds ``wkv_a``, dense where it holds no ``router``."""
    router = {"top_k": top_k, "n_group": n_group, "topk_group": topk_group, "scaling": scaling}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            h = _norm(x, layer["mixer_norm"].astype(F32), eps)
            if "w_in" in layer:
                x = x + _kda(
                    h, {n: layer[n].astype(F32) for n in _KDA}, heads=heads, key_dim=key_dim, conv=conv, eps=eps,
                    lower_bound=lower_bound, correct=correct, beta_one=beta_one, output_gate=output_gate, head_decay=head_decay,
                    softplus_gate=softplus_gate, state_dtype=state_dtype,
                )
            else:
                x = x + _latent_attention(
                    h, {n: layer[n].astype(F32) for n in _LATENT}, eps=eps, nope=nope, latent=latent, rope_theta=rope_theta,
                    head_gate=head_gate, query_block=query_block,
                )
            h = _norm(x, layer["ffn_norm"].astype(F32), eps)
            if "router" in layer:
                x = x + experts(h, layer, first_expert=first_expert, **router)
            else:
                x = x + _swiglu(h, *(layer[n].astype(F32) for n in ("w_gate", "w_up", "w_down")))
        x = _norm(x, weights["final_norm"].astype(F32), eps)
        return _logits(x, weights["head"], vocab_block)
