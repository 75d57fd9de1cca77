"""Plain reference: Nemotron-H's block as Nemotron-3-Super runs it.  A layer is
ONE norm, ONE mixer and one residual, ``x <- x + Mixer_i(RMSNorm_i(x))``, the
mixer by the letter of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (its
scan as the RECURRENCE only), ``*`` attention, ``E`` latent squared-ReLU experts.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no chunks, no quadratic form of
the scan, no cache, no kernel, no sorted rows, and no import from the program.
The block is the Nemotron-H report's (arXiv:2504.03624), the mixer the
published Mamba-2 (Dao and Gu, arXiv:2405.21060), every size a key of the
model's ``config.json`` (``model_type`` ``nemotron_h``)::

    h = RMSNorm(x)                                            (eps 1e-5, one weight vector)

    M:  [z | xBC | dt] = h W_in
        xBC_t = silu(sum_{i<K} w_i xBC_{t-K+1+i} + b)         (depthwise, causal, zeros before the start)
        x as H heads of P, B and C as G groups of N (a group's H / G heads share them)
        D_t = softplus(dt_t + dt_bias),  A = -exp(A_log)
        S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T              (a head's state, P x N, zero before the start)
        y_t = S_t C_t + D x_t
        g   = RMSNorm(y * silu(z)) over each of the G groups of channels, times one weight
        Mixer = g W_out

    *:  q = h Wq (H heads of K), k = h Wk, v = h Wv (G heads of K)
        causal softmax(q k^T K^-0.5) v, H / G queries a KV head; NO rotary and no other position term
        Mixer = o Wo

    E:  s = sigmoid(h Wr) over all experts;  picks = the top_k largest of s + beta (beta picks, never weighs)
        w_e = scaling * s_e / (sum over the picks of s + 1e-20)
        l = h W_a                                             (D -> L, the latent)
        Expert_e(l) = relu(l U_e)^2 V_e                       (U_e L x F, V_e F x L: two matrices, no gate)
        m = (sum over the picked e HELD here of w_e Expert_e(l)) W_b      (L -> D)
        Mixer = m + relu(h U_s)^2 V_s                         (the shared expert, on the full-width h)

    logits = RMSNorm(x) W_head

A picked expert that is not held (``first_expert .. first_expert + held - 1``
are) adds nothing.  ``W_b`` is linear and has no bias, so the shares of a
layer's experts add up to the uncut layer's ``m``; the shared expert and the
residual are every share's alike and count once.

The state is carried one token at a time under ``lax.scan``; the convolution
is ``K`` shifted sums; attention runs a KV head and a block of query rows at a
time; the held experts run one after the other on every token, each weighted
by what the token's picks give it (nothing where it was not picked), its two
matrices converted to float32 inside its own step: plain, not fast.

What ``forward`` can be told otherwise (the controls of the serving check:
each must come out not correct): ``latent`` False (no ``W_a`` / ``W_b``: the
experts on the first ``L`` columns of ``h``, their sum into those columns),
``act`` "silu" or "gated" (``silu(l U) * (l U)``) for "relu2", ``scaling`` 1,
``normalise`` False, ``bias_weighs`` True, ``shared`` False,
``expert_residual`` False (an ``E`` layer's ``x +`` dropped), ``norm_groups``
1 (the gated norm over all channels at once), ``shared_group`` True (B and C of
group 0 given to every head).

Weights: ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]`` and a layer's
``norm [D]`` with, by its kind, ``w_in [D, 2 W + 2 G N + H]``, ``conv_w [K, W +
2 G N]``, ``conv_b``, ``dt_bias / A_log / D [H]``, ``ssm_norm [W]``, ``w_out [W,
D]``; or ``wq [D, H, K]``, ``wk / wv [D, G, K]``, ``wo [H, K, D]``; or ``router
[D, E]``, ``router_bias [E]``, ``w_latent_in [D, L]``, ``w_latent_out [L, D]``,
``w_up [held, L, F]``, ``w_down [held, F, L]``, ``shared_w_up [D, Fs]``,
``shared_w_down [Fs, D]``.  A layer's kind is read off its keys.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
_STACKS = ("w_up", "w_down")  # the held experts' matrices: converted an expert at a time


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# -- M ---------------------------------------------------------------------------


def _recurrence(x, b, c, step, a, skip) -> jax.Array:
    """x [S, H, P], b / c [S, H, N] (a head's own group's), step [S, H], a [H],
    skip [H] -> y [S, H, P], one token at a time from an empty state."""

    def token(state, at):
        x_t, b_t, c_t, d_t = at
        state = jnp.exp(d_t * a)[:, None, None] * state + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + skip[:, None] * x_t

    empty = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), F32)
    return jax.lax.scan(token, empty, (x, b, c, step))[1]


def mamba2(h: jax.Array, w: Dict[str, jax.Array], *, heads: int, head_dim: int, d_state: int, groups: int, conv: int,
           eps: float, norm_groups: int, shared_group: bool) -> jax.Array:
    """The ``M`` mixer on the normed input ``h [S, D]`` -> ``[S, D]``."""
    s = h.shape[0]
    width, state = heads * head_dim, groups * d_state
    proj = h @ w["w_in"]
    z, xbc, dt = proj[:, :width], proj[:, width: 2 * width + 2 * state], proj[:, 2 * width + 2 * state:]
    before = jnp.pad(xbc, ((conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w["conv_w"][i] * before[i: i + s] for i in range(conv)) + w["conv_b"])
    x = xbc[:, :width].reshape(s, heads, head_dim)
    b = xbc[:, width: width + state].reshape(s, groups, d_state)
    c = xbc[:, width + state:].reshape(s, groups, d_state)
    of_head = jnp.zeros((heads,), jnp.int32) if shared_group else jnp.arange(heads) // (heads // groups)
    y = _recurrence(x, b[:, of_head], c[:, of_head], jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]), w["D"])
    g = (y.reshape(s, width) * jax.nn.silu(z)).reshape(s, norm_groups, width // norm_groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(s, width) * w["ssm_norm"]) @ w["w_out"]


# -- * ---------------------------------------------------------------------------


def attention(h: jax.Array, w: Dict[str, jax.Array], *, query_block: int) -> jax.Array:
    """The ``*`` mixer on the normed input ``h [S, D]`` -> ``[S, D]``: causal
    softmax attention, query head ``i`` reads KV head ``i // (H / G)``, and q
    and k go in as projected: no position term of any kind."""
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k, v = jnp.einsum("sd,dgk->sgk", h, w["wk"]), jnp.einsum("sd,dgk->sgk", h, w["wv"])
    s, heads, width = q.shape
    g = k.shape[1]
    block = min(query_block, s)
    blocks = -(-s // block)
    rows = jnp.pad(q, ((0, blocks * block - s), (0, 0), (0, 0))).reshape(blocks, block, g, heads // g, width)
    first = jnp.arange(blocks) * block

    def of_kv_head(args):
        q_g, k_g, v_g = args  # [blocks, block, n, K], [S, K], [S, K]

        def of_rows(rows_args):
            q_b, start = rows_args
            scores = jnp.einsum("ink,jk->nij", q_b, k_g) * width ** -0.5
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
            return jnp.einsum("nij,jk->ink", jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1), v_g)

        return jax.lax.map(of_rows, (q_g, first))

    out = jax.lax.map(of_kv_head, (rows.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(blocks * block, heads, width)[:s]  # [G, blocks, block, n, K] ->
    return jnp.einsum("shk,hkd->sd", out, w["wo"])


# -- E ---------------------------------------------------------------------------


def route(h: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scaling: float, normalise: bool = True,
          bias_weighs: bool = False):
    """(the picks ``[S, k]``, their weights ``[S, k]``): sigmoid scores over
    ALL experts, the ``top_k`` largest ``score + bias`` (one group: nothing is
    masked), the picks' own scores over their sum, times ``scaling``."""
    scores = jax.nn.sigmoid(h @ router)
    select = scores + bias[None, :]
    _, picks = jax.lax.top_k(select, top_k)
    top = jnp.take_along_axis(select if bias_weighs else scores, picks, axis=1)
    if normalise:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return picks, top * scaling


def _act(u: jax.Array, act: str) -> jax.Array:
    if act == "relu2":
        return jnp.square(jax.nn.relu(u))
    return jax.nn.silu(u) * u if act == "gated" else jax.nn.silu(u)  # the controls


def held_experts(l: jax.Array, w: Dict[str, jax.Array], picks: jax.Array, weights: jax.Array, first: int, act: str) -> jax.Array:
    """``sum over the picked e held here of w_e relu(l U_e)^2 V_e`` ``[S, L]``:
    every held expert on every token, one after the other."""

    def add_expert(y, expert):
        e, up, down = expert
        mine = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)  # [S]: nothing where e was not picked
        return y + mine[:, None] * (_act(l @ up.astype(F32), act) @ down.astype(F32)), None

    held = w["w_up"].shape[0]
    return jax.lax.scan(add_expert, jnp.zeros_like(l), (jnp.arange(held), w["w_up"], w["w_down"]))[0]


def expert_layer(h: jax.Array, w: Dict[str, jax.Array], *, first_expert: int, top_k: int, scaling: float, latent: bool = True,
                 act: str = "relu2", shared: bool = True, normalise: bool = True, bias_weighs: bool = False) -> jax.Array:
    """The ``E`` mixer on the normed input ``h [S, D]`` -> ``[S, D]``; ``w``
    float32 but for the two stacks of held experts."""
    picks, weights = route(h, w["router"], w["router_bias"], top_k=top_k, scaling=scaling, normalise=normalise, bias_weighs=bias_weighs)
    if latent:
        m = held_experts(h @ w["w_latent_in"], w, picks, weights, first_expert, act) @ w["w_latent_out"]
    else:  # the control: no projection either way
        width = w["w_latent_in"].shape[1]
        m = jnp.pad(held_experts(h[:, :width], w, picks, weights, first_expert, act), ((0, 0), (0, h.shape[1] - width)))
    return m + _act(h @ w["shared_w_up"], act) @ w["shared_w_down"] if shared else m


# -- the model --------------------------------------------------------------------


def forward(
    weights: Dict[str, Any], tokens: jax.Array, *, eps: float, heads: int, head_dim: int, d_state: int, groups: int,
    conv: int, top_k: int, scaling: float, first_expert: int, latent: bool = True, act: str = "relu2", shared: bool = True,
    normalise: bool = True, bias_weighs: bool = False, expert_residual: bool = True, norm_groups: int = 0,
    shared_group: bool = False, query_block: int = 512,
) -> jax.Array:
    """Logits ``[S, V]`` in float32 for one sequence of token ids ``[S]``."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            w = {k: v if k in _STACKS else v.astype(F32) for k, v in layer.items()}
            h = _rms_norm(x, w["norm"], eps)
            if "w_in" in w:
                x = x + mamba2(h, w, heads=heads, head_dim=head_dim, d_state=d_state, groups=groups, conv=conv, eps=eps,
                               norm_groups=norm_groups or groups, shared_group=shared_group)
            elif "wq" in w:
                x = x + attention(h, w, query_block=query_block)
            else:
                y = expert_layer(h, w, first_expert=first_expert, top_k=top_k, scaling=scaling, latent=latent, act=act,
                                 shared=shared, normalise=normalise, bias_weighs=bias_weighs)
                x = x + y if expert_residual else y
        x = _rms_norm(x, weights["final_norm"].astype(F32), eps)
        return x @ weights["head"].astype(F32)

