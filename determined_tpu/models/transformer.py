"""Flagship model: decoder-only transformer LM, TPU-first.

The reference platform ships no model internals (its deepest model hooks
are DeepSpeed pipeline/MPU passthrough, ``deepspeed/_mpu.py``).  This is
the framework's flagship: one module that runs DP / FSDP / TP / SP by
MeshConfig alone, with:

- logical-axis partitioning on every kernel (embed/heads/kv/mlp/vocab),
  resolved by LogicalAxisRules -> XLA inserts the collectives;
- activation sharding constraints (batch over dp/fsdp, seq over sp);
- rotary position embeddings (one base, or parameters per layer type with
  YaRN), GQA with a stated ``head_dim``, RMSNorm, SwiGLU;
- a type per layer: full causal attention, a sliding window, or power
  retention (``ops/retention.py``: gated attention of degree 2, whose serving
  cache is a fixed-size state a lane and not keys and values a token);
- experts: the top-2 capacity layer or dropless top-k over the experts a
  device holds (models/moe.py);
- attention dispatch: ring attention when the mesh has a "seq" axis,
  Pallas flash attention on TPU otherwise, reference for tiny seqs;
- bf16 compute with f32 params, per-block remat for long-context memory.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from determined_tpu.data import DataLoader, SyntheticDataset
from determined_tpu.ops.attention import (
    NEG_INF,
    _repeat_kv,
    dot_product_attention,
    reference_attention,
)
from determined_tpu.ops.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
    paged_latent_attention,
)
from determined_tpu.ops.retention import (
    retention_chunk,
    retention_decode,
    retention_quadratic,
    state_shapes,
)
from determined_tpu.ops.ring_attention import ring_attention
from determined_tpu.parallel.mesh import MeshAxes
from determined_tpu.parallel.sharding import with_sharding_constraint
from determined_tpu.train._trial import JaxTrial


#: the kinds of layer, under the names published configurations give them
FULL, SLIDING, RETENTION = "full_attention", "sliding_attention", "power_retention"
LAYER_TYPES = (FULL, SLIDING, RETENTION)
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None          # None -> n_heads (MHA)
    d_ff: Optional[int] = None                # None -> 4 * d_model
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16                 # activation/compute dtype
    attention_impl: str = "auto"              # auto|reference|flash|ring
    remat: bool = False
    rope_theta: float = 10000.0
    # width of one attention head; None -> d_model // n_heads
    head_dim: Optional[int] = None
    # one of LAYER_TYPES per block; None -> full causal attention everywhere.
    # A sliding layer's query i sees keys i - sliding_window < j <= i.
    # A power_retention layer keeps GQA's projections and adds a gate a KV
    # head (``wg``, no bias): its decay is sigmoid(gate + retention_gate_bias),
    # the bias a constant and no leaf (0: a fresh gate forgets half a step).
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    retention_gate_bias: float = 0.0
    # RMSNorm over each head of q and k before rotary, one learned weight of
    # head_dim each shared by the heads (Qwen3's); power_retention layers run it
    qk_norm: bool = False
    # rotary parameters per layer type (the published `rope_parameters`
    # group): {"full_attention": {"rope_type": "yarn", "rope_theta": ...,
    # "factor": ..., ...}, "sliding_attention": {"rope_type": "default",
    # "rope_theta": ...}}.  A type it does not name rotates by rope_theta; one
    # whose rope_type is "none" does not rotate at all (q and k as projected).
    rope_parameters: Any = None
    # MoE (models/moe.py): every moe_every-th block swaps its dense MLP
    # for experts; 0 = dense everywhere.  moe_top_k == 0 is the top-2
    # capacity layer (MoE, moe_capacity_factor); moe_top_k > 0 is dropless
    # top-k over the held experts (RoutedExperts): moe_experts is then the
    # router's width, moe_experts_held = (first, count) the range this
    # model's parameters hold (None: all) and moe_intermediate_size an
    # expert's width (None: d_ff)
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_top_k: int = 0
    moe_intermediate_size: Optional[int] = None
    moe_experts_held: Optional[Tuple[int, int]] = None
    # the first dense_prefix blocks keep their dense MLP whatever moe_every
    # says; the period of moe_every starts after them
    dense_prefix: int = 0
    # the router of the dropless layer: "softmax" (top-k of the softmax,
    # renormalised) or "sigmoid_grouped" (sigmoid scores, a selection bias,
    # the moe_topk_group best of moe_n_group groups, top-k inside them,
    # weights normalised and times moe_routed_scaling), and how many shared
    # experts of width moe_intermediate_size every token also passes through
    moe_router: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scaling: float = 1.0
    moe_shared_experts: int = 0
    # "sigmoid" is the plain form of the grouped router: sigmoid scores, the
    # top-k largest, weights normalised to one; no groups, no bias.  How the
    # shared experts' outputs combine: "sum", or "mean" (their sum over their
    # number)
    moe_shared_combine: str = "sum"
    # The block's norms: "rms" (no mean taken) or "layernorm" (mean and variance
    # over the features in float32, a weight and no bias), with norm_eps.
    # parallel_block: ONE norm a block, attention and the MLP or experts both
    # read it, ``x + Attn(LN(x)) + FFN(LN(x))``.  tie_embeddings: no lm_head,
    # the logits are the final norm times the embedding's transpose, times
    # logit_scale.
    norm: str = "rms"
    norm_eps: float = 1e-6
    parallel_block: bool = False
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    # Latent attention (MLA): kv_lora_rank set swaps every block's GQA for
    # it.  Queries come through a q_lora_rank bottleneck as n_heads heads of
    # [qk_nope_head_dim | qk_rope_head_dim]; keys and values are expanded from
    # ONE latent row a token, [kv_lora_rank | qk_rope_head_dim] (what serving
    # caches), to n_heads heads of [qk_nope_head_dim | v_head_dim], with the
    # row's rotary part shared by all heads.  softmax_scale: None -> the
    # query-key width ** -0.5
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    softmax_scale: Optional[float] = None
    # dtype of the parameters TransformerLM.init makes (and serving reads)
    param_dtype: Any = jnp.float32
    # Quantized matmul arithmetic (train/_quant.py): none|int8|fp8 routes
    # every dense/attention projection matmul (and the logits-path
    # lm_head) through per-channel dynamically-scaled reduced-precision
    # arithmetic with fp32 master weights.  The param tree is untouched
    # (a flax dot_general injection), so checkpoints and sharding specs
    # are byte-compatible across modes; composes with pipe (stage blocks
    # inherit the config).  The fused-CE lm_head contraction keeps its
    # own bf16 kernel.
    quantized_matmul: str = "none"
    # False under manual-SPMD pipeline stages: logical param annotations
    # are meaningless (and invalid) inside shard_map, where placement is
    # explicit
    partition_params: bool = True
    # Manual-SPMD axis names, set ONLY inside pipeline stages (shard_map):
    # seq_axis_name routes attention through ring_attention_local over that
    # axis (with globally-offset rope positions); expert_axis_name makes
    # MoE blocks run local-expert compute + psum-combine over that axis.
    seq_axis_name: Optional[str] = None
    expert_axis_name: Optional[str] = None

    def __post_init__(self):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(
                "moe_every must be >= 1 when moe_experts > 0 "
                f"(got moe_every={self.moe_every})"
            )
        setattr_ = lambda k, v: object.__setattr__(self, k, v)  # noqa: E731 (frozen)
        if self.head_dim is None:
            setattr_("head_dim", self.d_model // self.n_heads)
        if self.layer_types is not None:
            setattr_("layer_types", tuple(self.layer_types))
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types needs one of {LAYER_TYPES} for each of the "
                    f"{self.n_layers} layers (got {len(self.layer_types)}: {self.layer_types})"
                )
            if SLIDING in self.layer_types and not (self.sliding_window or 0) >= 1:
                raise ValueError("a sliding_attention layer needs sliding_window >= 1")
        if self.retention_layers and (
            self.kv_lora_rank is not None or self.parallel_block or self.head_dim % 2 or self.n_heads % self.kv_heads
            or self.seq_axis_name is not None
        ):
            raise ValueError(
                "a power_retention layer runs in a sequential block, without latent attention or a `seq` axis, "
                "on an even head_dim and whole groups of query heads a KV head"
            )
        if self.qk_norm and len(self.retention_layers) != self.n_layers:
            raise ValueError("qk_norm runs in power_retention layers only: every layer must be one")
        if isinstance(self.rope_parameters, Mapping):
            # hashable, so that the config can stay a static argument
            setattr_(
                "rope_parameters",
                tuple(sorted((t, tuple(sorted(p.items()))) for t, p in self.rope_parameters.items())),
            )
        for layer_type, params in self.rope_parameters or ():
            if layer_type not in LAYER_TYPES or dict(params).get("rope_type", "default") not in ("default", "yarn", "none"):
                raise ValueError(f"rope_parameters: unknown layer type or rope_type in {layer_type}: {dict(params)}")
        if self.moe_top_k:
            if not 1 <= self.moe_top_k <= self.moe_experts:
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} needs 1 <= moe_top_k <= moe_experts ({self.moe_experts})"
                )
            if self.moe_experts_held is not None:
                first, count = (int(v) for v in self.moe_experts_held)
                setattr_("moe_experts_held", (first, count))
                if first < 0 or count < 1 or first + count > self.moe_experts:
                    raise ValueError(
                        f"moe_experts_held={(first, count)} (first, count) must lie inside "
                        f"[0, {self.moe_experts})"
                    )
        elif self.moe_experts_held is not None or self.moe_intermediate_size is not None:
            raise ValueError("moe_experts_held and moe_intermediate_size belong to moe_top_k > 0")
        if self.moe_router not in ("softmax", "sigmoid", "sigmoid_grouped"):
            raise ValueError(f"moe_router is softmax, sigmoid or sigmoid_grouped (got {self.moe_router!r})")
        if (self.moe_router != "softmax" or self.moe_shared_experts) and not self.moe_top_k:
            raise ValueError("moe_router and moe_shared_experts belong to moe_top_k > 0")
        if self.moe_shared_combine not in ("sum", "mean") or self.norm not in ("rms", "layernorm"):
            raise ValueError(
                f"moe_shared_combine is sum or mean and norm is rms or layernorm "
                f"(got {self.moe_shared_combine!r}, {self.norm!r})"
            )
        if self.latent and (self.parallel_block or SLIDING in (self.layer_types or ())):
            raise ValueError("latent attention runs in a sequential block of full layers")
        if self.moe_router == "sigmoid_grouped":
            g, keep = self.moe_n_group, self.moe_topk_group
            if g < 1 or self.moe_experts % g or not 1 <= keep <= g or self.moe_experts // g < 2 or (
                self.moe_top_k > keep * (self.moe_experts // g)
            ):
                raise ValueError(
                    f"sigmoid_grouped: moe_n_group={g} must divide moe_experts={self.moe_experts} into "
                    f"groups of two or more, and moe_topk_group={keep} of them must hold moe_top_k={self.moe_top_k}"
                )
        if not 0 <= self.dense_prefix <= self.n_layers:
            raise ValueError(f"dense_prefix={self.dense_prefix} must lie in [0, n_layers]")
        if self.kv_lora_rank is not None:
            sizes = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
            if any(v is None or int(v) < 1 for v in sizes) or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs q_lora_rank, kv_lora_rank, qk_nope_head_dim, an even "
                    f"qk_rope_head_dim and v_head_dim (got {sizes})"
                )
            if self.quantized_matmul != "none" or self.seq_axis_name is not None:
                raise ValueError("latent attention runs without quantized_matmul and outside a `seq` axis")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    def layer_type(self, i: int) -> str:
        return FULL if self.layer_types is None else self.layer_types[i]

    def use_moe(self, i: int) -> bool:
        """Whether block ``i`` holds experts: after the dense prefix, every
        ``moe_every``-th block."""
        j = i - self.dense_prefix
        return self.moe_experts > 0 and j >= 0 and (j % self.moe_every) == self.moe_every - 1

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def rope_dim(self) -> int:
        """The width rotary embeddings turn: a head, or a latent row's rotary part."""
        return self.qk_rope_head_dim if self.latent else self.head_dim

    @property
    def attn_scale(self) -> float:
        if self.softmax_scale is not None:
            return float(self.softmax_scale)
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim) if self.latent else self.head_dim) ** -0.5

    def window(self, layer_type: str) -> Optional[int]:
        return self.sliding_window if layer_type == SLIDING else None

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """The sliding-window layers, in order: serving keeps their keys and
        values in a store of its own (``init_kv_cache``)."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) == SLIDING)

    @property
    def retention_layers(self) -> Tuple[int, ...]:
        """The power-retention layers, in order: serving keeps a state a decode
        lane for each (``init_kv_cache``), and no token's keys or values."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) == RETENTION)

    @property
    def paged_layers(self) -> int:
        """How many layers keep a token's rows in the paged pool."""
        return self.n_layers - len(self.window_layers) - len(self.retention_layers)

    def cache_index(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its own type: its index in
        the paged pool (full layers), in the window store (sliding layers) or
        in the state pool (retention layers)."""
        return sum(1 for j in range(i) if self.layer_type(j) == self.layer_type(i))

    def rope(self, layer_type: str) -> Optional["Rope"]:
        """How a layer of this type rotates q and k; None: it does not."""
        params = dict(dict(self.rope_parameters or ()).get(layer_type, ()))
        theta = float(params.get("rope_theta", self.rope_theta))
        if params.get("rope_type", "default") == "none":
            return None
        if params.get("rope_type", "default") == "default":
            return Rope(theta)
        return Rope(
            theta,
            tuple(yarn_inv_freq(self.rope_dim, theta, **{k: params[k] for k in _YARN_KEYS})),
            float(params["attention_factor"]),
        )


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary parameters of one layer type: the base, or stated inverse
    frequencies (YaRN) with the factor cos and sin are multiplied by."""

    theta: float
    inv_freq: Optional[Tuple[float, ...]] = None
    attention_factor: float = 1.0


def yarn_inv_freq(
    head_dim: int, theta: float, *, factor: float, original_max_position_embeddings: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's inverse frequencies (Peng et al. 2023, as the published
    configurations compute them), static: applied at every length.  Pair i
    blends the interpolated frequency ``theta^(-2i/d) / factor`` (slow pairs,
    past ``high``) with the plain one (fast pairs, before ``low``), where
    ``corr(n) = d ln(original / (2 pi n)) / (2 ln theta)`` is the pair that
    turns n times over the original context."""
    half = head_dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)

    def corr(rotations: float) -> float:
        return head_dim * math.log(original_max_position_embeddings / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rope(x: jax.Array, positions: jax.Array, rope: Optional[Rope]) -> jax.Array:
    """Rotary embeddings on [b, h, s, d], as a layer type's ``rope`` states
    them: its base, or its frequencies and the factor on cos and sin; None
    leaves ``x`` as it is (a layer type without positions).
    ``positions`` is [s] where every row of the batch sits at the same ones,
    or [b, s] where each row has its own (the decode step's lanes)."""
    if rope is None:
        return x
    d = x.shape[-1]
    if rope.inv_freq is not None:
        freqs = jnp.asarray(rope.inv_freq, jnp.float32)
    else:
        freqs = rope.theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs[None, :]  # [(b,) s, d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if rope.attention_factor != 1.0:
        cos, sin = cos * rope.attention_factor, sin * rope.attention_factor
    if positions.ndim == 2:  # [b, s, d/2] against x's [b, h, s, d/2]
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rx1 = x1 * cos - x2 * sin
    rx2 = x1 * sin + x2 * cos
    return jnp.stack([rx1, rx2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm's numerics, stated once: float32 mean of squares, the rest in x's dtype."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def _layer_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """LayerNorm without a bias: mean and variance over the features in float32, the result in x's dtype."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _maybe_partition(partition: bool, init, names):
    """with_partitioning when annotations apply; plain init under manual
    SPMD (pipeline stages inside shard_map)."""
    return nn.with_partitioning(init, names) if partition else init


class RMSNorm(nn.Module):
    """A block's norm: RMSNorm, or under ``kind`` "layernorm" a LayerNorm
    without a bias (one leaf, ``scale``, either way)."""

    eps: float = 1e-6
    partition: bool = True
    param_dtype: Any = jnp.float32
    kind: str = "rms"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale",
            _maybe_partition(self.partition, nn.initializers.ones, ("embed",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        with jax.named_scope("block.norm"):
            return (_rms if self.kind == "rms" else _layer_norm)(x, scale, self.eps)


class Attention(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None  # jax.sharding.Mesh when ring attention is in play
    layer_type: str = FULL

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.head_dim
        from determined_tpu.train._quant import make_dot_general

        qdg = make_dot_general(cfg.quantized_matmul)
        dense = lambda feats, logical, name: nn.DenseGeneral(  # noqa: E731
            feats,
            axis=-1,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            dot_general=qdg,
            kernel_init=_maybe_partition(
                cfg.partition_params, nn.initializers.lecun_normal(), logical
            ),
            name=name,
        )
        with jax.named_scope("attn.qkv"):
            q = dense((cfg.n_heads, hd), ("embed", "heads", "head_dim"), "wq")(x)
            k = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wk")(x)
            v = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wv")(x)
            # [b, s, h, d] -> [b, h, s, d]
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

            positions = jnp.arange(s)
            if cfg.seq_axis_name is not None:
                # manual SPMD inside a pipeline stage: s is the LOCAL shard
                # length; rope positions are global (contiguous assignment)
                positions = positions + jax.lax.axis_index(cfg.seq_axis_name) * s
            rope = cfg.rope(self.layer_type)
            q = _rope(q, positions, rope)
            k = _rope(k, positions, rope)
        window = cfg.window(self.layer_type)

        impl = cfg.attention_impl
        use_ring = (
            impl == "ring"
            or (
                impl == "auto"
                and self.mesh is not None
                and self.mesh.shape.get(MeshAxes.SEQUENCE, 1) > 1
            )
        )
        if window is not None and (cfg.seq_axis_name is not None or use_ring):
            raise ValueError(
                "ring attention (a `seq` mesh axis, attention: ring) knows no "
                f"sliding window: layer type {self.layer_type!r} cannot run under it"
            )
        # named for the device trace: the two kinds cost differently (a ring has no window)
        with jax.named_scope("attn.window" if window is not None else "attn.full"):
            if cfg.seq_axis_name is not None:
                # already inside shard_map over the seq axis: run the ring on
                # local shards (zigzag-balanced for causal)
                from determined_tpu.ops.ring_attention import ring_attention_local

                out = ring_attention_local(
                    q, k, v, axis_name=cfg.seq_axis_name, causal=True
                )
            elif use_ring:
                if self.mesh is None:
                    raise ValueError("ring attention requires the mesh")
                out = ring_attention(q, k, v, self.mesh, causal=True)
            else:
                out = dot_product_attention(
                    q, k, v, causal=True, impl=impl, mesh=self.mesh, window=window
                )
        with jax.named_scope("attn.out"):
            out = out.transpose(0, 2, 1, 3)  # [b, s, h, d]
            return nn.DenseGeneral(
                cfg.d_model,
                axis=(-2, -1),
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                dot_general=qdg,
                kernel_init=_maybe_partition(
                    cfg.partition_params,
                    nn.initializers.lecun_normal(),
                    ("heads", "head_dim", "embed"),
                ),
                name="wo",
            )(out)


def _gate_log(cfg: TransformerConfig, gate: jax.Array) -> jax.Array:
    """The logarithm of a retention layer's decay, float32, from the gate's
    projection ``[..., kv_heads]``."""
    return jax.nn.log_sigmoid(gate.astype(jnp.float32) + cfg.retention_gate_bias)


class Retention(nn.Module):
    """A power-retention layer over the whole sequence, in its quadratic form
    (``ops/retention.py retention_quadratic``): GQA's projections, RMSNorm a
    head on q and k (``qk_norm``), rotary, a gate a KV head.  What ``init``
    builds for serving, the wide oracle of the serving forward below (which
    reads the same leaves and runs the recurrent form), and training."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        hd = cfg.head_dim
        from determined_tpu.train._quant import make_dot_general

        qdg = make_dot_general(cfg.quantized_matmul)
        dense = lambda feats, logical, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            feats, axis=axis, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, dot_general=qdg,
            kernel_init=_maybe_partition(cfg.partition_params, nn.initializers.lecun_normal(), logical), name=name,
        )
        with jax.named_scope("attn.qkv"):
            q = dense((cfg.n_heads, hd), ("embed", "heads", "head_dim"), "wq")(x)
            k = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wk")(x)
            v = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wv")(x)
            log_g = _gate_log(cfg, dense(cfg.kv_heads, ("embed", "kv"), "wg")(x)).transpose(0, 2, 1)  # [b, kv, s]
            if cfg.qk_norm:
                ones = _maybe_partition(cfg.partition_params, nn.initializers.ones, ("head_dim",))
                q = _rms(q, self.param("q_norm", ones, (hd,), cfg.param_dtype), cfg.norm_eps)
                k = _rms(k, self.param("k_norm", ones, (hd,), cfg.param_dtype), cfg.norm_eps)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            positions, rope = jnp.arange(x.shape[1]), cfg.rope(RETENTION)
            q, k = _rope(q, positions, rope), _rope(k, positions, rope)
        with jax.named_scope("attn.retention"):
            out = retention_quadratic(q, k, v, log_g)
        with jax.named_scope("attn.out"):
            return dense(cfg.d_model, ("heads", "head_dim", "embed"), "wo", (-2, -1))(out.transpose(0, 2, 1, 3))


def _latent_param_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Any, ...], Any]]:
    """Latent attention's leaves: name -> (shape, logical axes, initialiser)."""
    d, h = cfg.d_model, cfg.n_heads
    qk, rope = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
    kernel, ones = nn.initializers.lecun_normal(), nn.initializers.ones
    return {
        "wq_a": ((d, cfg.q_lora_rank), ("embed", None), kernel),
        "q_norm": ((cfg.q_lora_rank,), (None,), ones),
        "wq_b": ((cfg.q_lora_rank, h, qk), (None, "heads", "head_dim"), kernel),
        "wkv_a": ((d, cfg.kv_lora_rank + rope), ("embed", None), kernel),
        "kv_norm": ((cfg.kv_lora_rank,), (None,), ones),
        "wkv_b": ((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim), (None, "heads", "head_dim"), kernel),
        "wo": ((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed"), nn.initializers.lecun_normal(in_axis=(0, 1))),
    }


class LatentAttention(nn.Module):
    """Multi-head latent attention over the whole sequence (training, and
    what ``init`` builds for serving): the projections of ``_latent_project``
    and the expanded, causal form of ``_latent_attend_local``; the serving
    forward below reads the same leaves."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        p = {
            name: self.param(name, _maybe_partition(cfg.partition_params, init, logical), shape, cfg.param_dtype)
            for name, (shape, logical, init) in _latent_param_shapes(cfg).items()
        }
        with jax.named_scope("attn.qkv"):
            q_nope, q_rope, c_kv, k_r = _latent_project(cfg, p, x, jnp.arange(x.shape[1]), cfg.rope(FULL))
        with jax.named_scope("attn.full"):
            out = _latent_attend_local(cfg)(q_nope, q_rope, c_kv, k_r, p["wkv_b"], None, 0)
        with jax.named_scope("attn.out"):
            return jnp.einsum("bshv,hvD->bsD", out, p["wo"].astype(cfg.dtype))


class MLP(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        from determined_tpu.train._quant import make_dot_general

        qdg = make_dot_general(cfg.quantized_matmul)
        dense = lambda feats, logical, name: nn.Dense(  # noqa: E731
            feats,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            dot_general=qdg,
            kernel_init=_maybe_partition(
                cfg.partition_params, nn.initializers.lecun_normal(), logical
            ),
            name=name,
        )
        with jax.named_scope("mlp.dense"):
            gate = dense(cfg.ff_dim, ("embed", "mlp"), "w_gate")(x)
            up = dense(cfg.ff_dim, ("embed", "mlp"), "w_up")(x)
            h = nn.silu(gate) * up
            if cfg.partition_params:
                h = with_sharding_constraint(h, ("batch", "length", "mlp"), mesh=self.mesh)
            return dense(cfg.d_model, ("mlp", "embed"), "w_down")(h)


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None
    use_moe: bool = False
    layer_type: str = FULL

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.norm_eps, partition=cfg.partition_params, param_dtype=cfg.param_dtype, kind=cfg.norm, name=name
        )

        def ffn(h: jax.Array) -> Tuple[jax.Array, jax.Array]:
            """The block's MLP or experts on the normed input, and the auxiliary loss."""
            if self.use_moe and cfg.moe_top_k:
                from determined_tpu.models.moe import RoutedExperts

                return RoutedExperts(
                    num_experts=cfg.moe_experts,
                    top_k=cfg.moe_top_k,
                    d_ff=cfg.moe_intermediate_size or cfg.ff_dim,
                    held=cfg.moe_experts_held,
                    dtype=cfg.dtype,
                    partition=cfg.partition_params,
                    expert_axis_name=cfg.expert_axis_name,
                    router_kind=cfg.moe_router,
                    n_group=cfg.moe_n_group,
                    topk_group=cfg.moe_topk_group,
                    routed_scaling=cfg.moe_routed_scaling,
                    shared_experts=cfg.moe_shared_experts,
                    shared_combine=cfg.moe_shared_combine,
                    param_dtype=cfg.param_dtype,
                    name="moe",
                )(h)
            if self.use_moe:
                from determined_tpu.models.moe import MoE

                return MoE(
                    num_experts=cfg.moe_experts,
                    d_ff=cfg.ff_dim,
                    capacity_factor=cfg.moe_capacity_factor,
                    dtype=cfg.dtype,
                    partition=cfg.partition_params,
                    expert_axis_name=cfg.expert_axis_name,
                    name="moe",
                )(h)
            return MLP(cfg, self.mesh, name="mlp")(h), jnp.zeros((), jnp.float32)

        h = norm("ln1")(x)
        if cfg.latent:
            att = LatentAttention(cfg, name="attn")(h)
        elif self.layer_type == RETENTION:
            att = Retention(cfg, name="attn")(h)
        else:
            att = Attention(cfg, self.mesh, self.layer_type, name="attn")(h)
        if cfg.parallel_block:
            # one norm: the MLP or the experts read what attention read
            y, aux = ffn(h)
            x = x + att + y
        else:
            x = x + att
            y, aux = ffn(norm("ln2")(x))
            x = x + y
        if cfg.partition_params:
            x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=self.mesh)
        return x, aux


class TransformerLM(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        return_hidden: bool = False,
        return_aux: bool = False,
    ) -> Any:
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=_maybe_partition(
                cfg.partition_params,
                nn.initializers.normal(stddev=0.02),
                ("vocab", "embed"),
            ),
            name="embed",
        )
        with jax.named_scope("lm.embed"):
            x = embed(tokens)
            if cfg.partition_params:
                x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=self.mesh)
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, prevent_cse=False)
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            x, aux = block_cls(cfg, self.mesh, cfg.use_moe(i), cfg.layer_type(i), name=f"block_{i}")(x)
            aux_total = aux_total + aux
        x = RMSNorm(
            eps=cfg.norm_eps, partition=cfg.partition_params, param_dtype=cfg.param_dtype, kind=cfg.norm, name="ln_f"
        )(x)
        if cfg.tie_embeddings:
            # no lm_head leaf: the embedding's transpose is the head (a fused-CE
            # caller contracts the hidden state with it, times logit_scale)
            if return_hidden:
                return (x, aux_total) if return_aux else x
            with jax.named_scope("loss.ce"):
                out = (embed.attend(x) * cfg.logit_scale).astype(jnp.float32)
            return (out, aux_total) if return_aux else out
        from determined_tpu.train._quant import make_dot_general

        lm_head = nn.Dense(
            cfg.vocab_size,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            dot_general=make_dot_general(cfg.quantized_matmul),
            kernel_init=_maybe_partition(
                cfg.partition_params, nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="lm_head",
        )
        if return_hidden:
            # fused-CE path: the caller contracts x with lm_head's kernel
            # chunk-by-chunk (ops/cross_entropy.py) so [b, s, vocab] logits
            # never hit HBM.  Init always takes the logits path, so the
            # param tree includes lm_head either way.
            return (x, aux_total) if return_aux else x
        with jax.named_scope("loss.ce"):  # the head's product: the loss's, fused or not
            out = lm_head(x).astype(jnp.float32)
        return (out, aux_total) if return_aux else out


def split_pipeline_params(
    boxed_params: Any, n_stages: int, virtual_stages: int = 1
) -> Dict[str, Any]:
    """Restructure a plain ``TransformerLM`` param tree for pipeline stages.

    Input: the tree from ``TransformerLM.init`` (possibly flax-``Partitioned``
    boxed).  Output: ``{"outer": <embed/ln_f/lm_head, boxes kept>, "blocks":
    {"layer_j": <layer j of every chunk stacked on a leading [P, ...] dim>}}``
    for j in [0, layers_per_chunk) — the per-layer dict (instead of an extra
    stacked lps dim) lets DENSE and MOE layers coexist in one chunk: layer j
    must have the same param structure across chunks (requiring the MoE
    period to divide layers-per-chunk), but different j's may differ.

    ``virtual_stages`` > 1 (the circular-interleaved schedule) splits the
    stack into P*V chunks and stacks leaves as ``[P, V, ...]`` —
    ``[p, v]`` holds chunk ``v*P + p``, i.e. pipe rank p's V NON-adjacent
    layer blocks (``parallel/pipeline.py`` ``stack_chunk_params`` layout).

    Because the stacked leaves are built from the SAME initialized values as
    the flat ``block_i`` subtrees, a pipe>1 trial initializes identically to
    pipe=1 — the basis of the loss-parity tests.
    """
    from flax.core import meta as flax_meta

    from determined_tpu.config.experiment import InvalidExperimentConfig

    tree = dict(boxed_params["params"])
    block_keys = sorted(
        (k for k in tree if k.startswith("block_")), key=lambda k: int(k.split("_")[1])
    )
    n_layers = len(block_keys)
    chunks_total = n_stages * virtual_stages
    if n_layers == 0 or n_layers % chunks_total:
        raise InvalidExperimentConfig(
            f"n_layers={n_layers} not divisible into {chunks_total} pipeline "
            f"chunks (pipe={n_stages} x virtual_stages={virtual_stages})"
        )
    lpc = n_layers // chunks_total
    blocks = [flax_meta.unbox(tree.pop(k)) for k in block_keys]
    stacked = {}
    for j in range(lpc):
        # chunk c covers layers [c*lpc, (c+1)*lpc); chunk order is the
        # order the microbatch traverses them
        layer_j = [blocks[c * lpc + j] for c in range(chunks_total)]
        structures = {jax.tree.structure(t) for t in layer_j}
        if len(structures) > 1:
            raise InvalidExperimentConfig(
                f"layer {j} differs in structure across pipeline chunks "
                "(is the MoE period a divisor of layers-per-chunk?)"
            )
        if virtual_stages == 1:
            stacked[f"layer_{j}"] = jax.tree.map(
                lambda *ls: jnp.stack(ls), *layer_j
            )
        else:
            stacked[f"layer_{j}"] = jax.tree.map(
                lambda *ls: jnp.stack(
                    [
                        jnp.stack(
                            [ls[v * n_stages + p] for v in range(virtual_stages)]
                        )
                        for p in range(n_stages)
                    ]
                ),
                *layer_j,
            )
    outer = {"params": tree}
    extra = {k: v for k, v in boxed_params.items() if k != "params"}
    if extra:
        outer.update(extra)
    return {"outer": outer, "blocks": stacked}


def pipeline_forward(
    cfg: TransformerConfig,
    mesh: Any,
    params: Dict[str, Any],
    tokens: jax.Array,
    num_microbatches: int,
    return_hidden: bool = False,
    rules: Any = None,
    return_aux: bool = False,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> Any:
    """Forward pass with the transformer blocks pipelined over ``pipe``.

    ``params`` is the ``split_pipeline_params`` layout.  Embed / final norm /
    lm_head run as ordinary SPMD computation outside the pipeline (sharded by
    their logical annotations); only the block stack rides the microbatch
    schedule (``parallel/pipeline.py`` — gpipe, 1f1b, or circular
    interleaved per ``schedule``/``virtual_stages``).  Stage block params
    are sharded over ``pipe`` (expert weights additionally over ``expert``)
    inside the schedule's ``shard_map``; the batch stays sharded over
    data/fsdp and the sequence over ``seq`` — ring attention runs inside
    each stage over the seq axis, and MoE combine psums over the expert
    axis intra-stage.  (FSDP sharding of block *params* does not compose
    yet.)  The reference's DeepSpeed grid composes PP only with DP/TP
    (``deepspeed/_mpu.py:9-50``).
    """
    from flax.core import meta as flax_meta

    from determined_tpu.parallel.pipeline import pipeline_apply

    outer = flax_meta.unbox(params["outer"])["params"]
    blocks = params["blocks"]
    lps = len(blocks)
    layer_keys = [f"layer_{j}" for j in range(lps)]
    has_moe = [isinstance(blocks[k], dict) and "moe" in blocks[k] for k in layer_keys]

    seq_n = mesh.shape.get(MeshAxes.SEQUENCE, 1) if mesh is not None else 1
    exp_n = mesh.shape.get(MeshAxes.EXPERT, 1) if mesh is not None else 1
    if exp_n > 1 and any(has_moe) and cfg.moe_experts % exp_n:
        raise ValueError(
            f"moe_experts={cfg.moe_experts} not divisible by expert axis {exp_n}"
        )

    emb = nn.Embed(
        cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, param_dtype=jnp.float32
    )
    with jax.named_scope("lm.embed"):
        x = emb.apply({"params": outer["embed"]}, tokens)
        x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=mesh, rules=rules)

    stage_cfg = dataclasses.replace(
        cfg,
        partition_params=False,
        attention_impl="auto" if cfg.attention_impl == "ring" else cfg.attention_impl,
        seq_axis_name=MeshAxes.SEQUENCE if seq_n > 1 else None,
        expert_axis_name=MeshAxes.EXPERT if exp_n > 1 else None,
    )

    def make_block_step(use_moe: bool, layer_type: str):
        blk = Block(stage_cfg, use_moe=use_moe, layer_type=layer_type)

        def block_step(p, h):
            return blk.apply({"params": p}, h)

        if cfg.remat:
            block_step = jax.checkpoint(block_step, prevent_cse=False)
        return block_step

    # layer j of every chunk is one stacked leaf: LMTrial._cfg has checked
    # that the period of layer_types divides layers-per-chunk
    steps = [make_block_step(m, cfg.layer_type(j)) for j, m in enumerate(has_moe)]
    want_aux = any(has_moe)

    def stage_fn(stage_params, h):
        aux = jnp.zeros((), jnp.float32)
        for j, key in enumerate(layer_keys):
            h, a = steps[j](stage_params[key], h)
            aux = aux + a
        return (h, aux) if want_aux else h

    out = pipeline_apply(
        stage_fn, blocks, x, mesh, num_microbatches, with_aux=want_aux,
        schedule=schedule, virtual_stages=virtual_stages,
    )
    x, aux = out if want_aux else (out, jnp.zeros((), jnp.float32))
    x = RMSNorm(partition=False).apply({"params": outer["ln_f"]}, x)
    if return_hidden:
        return (x, aux) if return_aux else x
    from determined_tpu.train._quant import make_dot_general

    head = nn.Dense(
        cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
        dot_general=make_dot_general(cfg.quantized_matmul),
    )
    with jax.named_scope("loss.ce"):
        logits = head.apply({"params": outer["lm_head"]}, x).astype(jnp.float32)
    return (logits, aux) if return_aux else logits


# ---------------------------------------------------------------------------
# KV-cache decode path (online serving: determined_tpu/serve)
# ---------------------------------------------------------------------------
#
# Training/eval run the full-sequence forward above; serving needs the
# autoregressive form: prefill the prompt once, then one-token decode steps
# reading/writing a **paged** KV cache (vLLM's PagedAttention layout, Kwon
# et al., SOSP '23).  The cache is a pool of fixed-size blocks
# ``[n_layers, num_blocks, block_size, kv_heads * head_dim]`` (a block is one
# contiguous ``[block_size, kv_heads * head_dim]`` slab: what the decode
# kernel in ``ops/paged_attention.py`` copies in one DMA and feeds the MXU
# as it lies); each sequence owns a *block table* mapping its logical block
# index to a physical block id.  Everything below is a pure function over
# the UNBOXED param tree that
# ``TransformerLM.init`` produces (the ``["params"]`` subtree), so the
# serve engine can jit prefill/decode with static shapes — batch lanes,
# table width, and prompt padding are fixed by ServeConfig, and the decode
# step traces exactly once no matter how request lengths mix (guarded by
# the RetraceSentinel in ``serve/engine.py``).
#
# Physical block 0 is a scratch block the allocator never hands out:
# padded prefill positions and inactive decode lanes write there, keeping
# the scatter shape static without masking arithmetic inside the kernel.


def latent_row_width(cfg: TransformerConfig) -> int:
    """Columns of the latent pool's row: ``kv_lora_rank + qk_rope_head_dim``
    values a token, padded with zeros to whole 128-lane tiles (576 -> 640 at
    the published widths) so that the decode kernel's copies and products are
    lane-aligned; an unpadded last dimension would be padded by the device's
    own tiled layout all the same."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def kv_cache_shape(cfg: TransformerConfig, num_blocks: int, block_size: int) -> Tuple[int, ...]:
    """One pool array's shape: K (and V) rows of a GQA model's full-attention
    layers (every layer, where none slides), or the latent rows."""
    width = latent_row_width(cfg) if cfg.latent else cfg.kv_heads * cfg.head_dim
    return (cfg.paged_layers, num_blocks, block_size, width)


def window_ring_blocks(cfg: TransformerConfig, block_size: int, chunk_tokens: int) -> int:
    """Blocks of ONE lane's ring in the window store: the window in whole
    blocks and one prefill chunk (a chunk's keys are written before its queries
    attend, and must not land on a key the chunk's first query still sees)."""
    return -(-cfg.sliding_window // block_size) + chunk_tokens // block_size


def window_store_shape(cfg: TransformerConfig, lanes: int, block_size: int, chunk_tokens: int) -> Tuple[int, ...]:
    """The window layers' store: ``[n_window, lanes * ring_blocks, block_size,
    kv_heads * head_dim]``.  Lane ``l`` owns the blocks ``[l * ring_blocks, (l
    + 1) * ring_blocks)`` as a ring: the token at position ``p`` lies in slot
    ``p % ring_tokens`` of it, whatever the context, so a lane never holds more
    than ``ring_tokens = ring_blocks * block_size`` tokens a layer.  Laid out
    as a pool of blocks so that the paged kernels read it as they read the pool."""
    return (len(cfg.window_layers), lanes * window_ring_blocks(cfg, block_size, chunk_tokens), block_size, cfg.kv_heads * cfg.head_dim)


def kv_bytes_per_token(cfg: TransformerConfig) -> int:
    """Bytes of cache a token owns over all layers that cache tokens, as
    attention reads them (a latent row's padding is not counted; in a window
    layer a token owns them only while it is inside the window; a retention
    layer caches no token: ``state_bytes_per_slot``)."""
    values = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) if cfg.latent else 2 * cfg.kv_heads * cfg.head_dim
    return (cfg.n_layers - len(cfg.retention_layers)) * values * jnp.dtype(cfg.dtype).itemsize


#: the dtype of a retention layer's state and normaliser: sums over a whole context
STATE_DTYPE = jnp.float32


def state_pool_shapes(cfg: TransformerConfig, lanes: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The retention layers' state pool and its normaliser (``ops/retention.py
    state_shapes``): a slot a decode lane a layer."""
    return state_shapes(len(cfg.retention_layers), lanes, cfg.kv_heads, cfg.head_dim)


def state_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Bytes of ONE retention layer's state and normaliser in one lane: what a
    request holds of such a layer whatever its length (0 without such layers)."""
    if not cfg.retention_layers:
        return 0
    state, norm = state_pool_shapes(cfg, 1)
    return (math.prod(state[1:]) + math.prod(norm[1:])) * jnp.dtype(STATE_DTYPE).itemsize


def init_kv_cache(
    cfg: TransformerConfig, num_blocks: int, block_size: int, lanes: Optional[int] = None,
    chunk_tokens: Optional[int] = None,
) -> Dict[str, jax.Array]:
    """Zeroed cache in the model's compute dtype.  The paged pool: ``k`` and
    ``v`` (keys are stored post-rope, i.e. exactly what attention consumes), or,
    under latent attention, ONE array ``kv`` whose row is ``[c_kv after its norm
    | k_r after rope | zeros]``.  A model with sliding-window layers holds a
    cache of two kinds: the pool keeps its full layers alone, addressed by block
    table, and ``wk`` / ``wv`` are the window layers' store
    (:func:`window_store_shape`: a ring a lane, for ``lanes`` decode lanes and
    prefill chunks of ``chunk_tokens``), addressed by lane and position.
    Power-retention layers make a third kind: ``rs`` / ``rz``, a float32 state
    and its normaliser a lane a layer (:func:`state_pool_shapes`), addressed
    by lane alone.  A model none of whose layers reads the pool gets none."""
    shape = kv_cache_shape(cfg, num_blocks, block_size)
    if cfg.latent:
        return {"kv": jnp.zeros(shape, cfg.dtype)}
    cache = {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)} if cfg.paged_layers else {}
    if cfg.retention_layers:
        if lanes is None:
            raise ValueError("a model with power-retention layers needs its lanes to size the state pool")
        state, norm = state_pool_shapes(cfg, lanes)
        cache.update(rs=jnp.zeros(state, STATE_DTYPE), rz=jnp.zeros(norm, STATE_DTYPE))
    if cfg.window_layers:
        if lanes is None or chunk_tokens is None or chunk_tokens % block_size:
            raise ValueError(
                "a model with sliding-window layers needs its lanes and its prefill chunk (whole blocks) "
                f"to size the window store (got lanes={lanes}, chunk_tokens={chunk_tokens})"
            )
        ring = window_store_shape(cfg, lanes, block_size, chunk_tokens)
        cache.update(wk=jnp.zeros(ring, cfg.dtype), wv=jnp.zeros(ring, cfg.dtype))
    return cache


def _block_size(cache: Dict[str, jax.Array]) -> Optional[int]:
    """Tokens a block of the paged pool; None where the cache has no pool."""
    pool = cache.get("kv", cache.get("k"))
    return None if pool is None else pool.shape[2]


def _rms_apply(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with the exact numerics of the ``RMSNorm`` module."""
    with jax.named_scope("serve.norm"):
        return _rms(x, scale, eps)


def _norm_apply(cfg: TransformerConfig, x: jax.Array, scale: jax.Array) -> jax.Array:
    """A block's (or the final) norm as the configuration states it."""
    if cfg.norm == "rms":
        return _rms_apply(x, scale, cfg.norm_eps)
    with jax.named_scope("serve.norm"):
        return _layer_norm(x, scale, cfg.norm_eps)


def _attn_proj(p: Dict[str, Any], x: jax.Array, dtype: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q/k/v projections as ``Attention`` computes them, to [b, heads, s, d]."""
    # under the caller's scope (``serve.attn.qkv``, with the rope that follows)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"]["kernel"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"]["kernel"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"]["kernel"].astype(dtype))
    return q, k, v


def _retention_proj(cfg: TransformerConfig, p: Dict[str, Any], h: jax.Array, positions: jax.Array):
    """A retention layer's q, k, v ``[b, heads, s, d]`` as ``Retention`` makes
    them (a norm a head, rotary) and the gate's logarithm ``[b, kv_heads, s]``."""
    q, k, v = _attn_proj(p, h, cfg.dtype)
    log_g = _gate_log(cfg, h @ p["wg"]["kernel"].astype(cfg.dtype)).transpose(0, 2, 1)
    if cfg.qk_norm:
        q, k = _rms(q, p["q_norm"], cfg.norm_eps), _rms(k, p["k_norm"], cfg.norm_eps)
    rope = cfg.rope(RETENTION)
    return _rope(q, positions, rope), _rope(k, positions, rope), v, log_g


def _mlp_apply(p: Dict[str, Any], x: jax.Array, dtype: Any) -> jax.Array:
    gate = x @ p["w_gate"]["kernel"].astype(dtype)
    up = x @ p["w_up"]["kernel"].astype(dtype)
    return (nn.silu(gate) * up) @ p["w_down"]["kernel"].astype(dtype)


def _check_decodable(cfg: TransformerConfig) -> None:
    if cfg.moe_experts > 0 and not cfg.moe_top_k:
        raise ValueError(
            "KV-cache serving runs dropless experts (moe_top_k > 0); the top-2 capacity "
            "layer drops tokens by the batch they arrive in and is not served"
        )
    if cfg.seq_axis_name is not None or cfg.expert_axis_name is not None:
        raise ValueError("KV-cache serving runs outside pipeline stages")


def _pool_rows(x: jax.Array, lead: Tuple[int, ...]) -> jax.Array:
    """Projected k or v ``[b, kv_heads, s, head_dim]`` as the pool stores a
    token, ``[*lead, kv_heads * head_dim]``: ``lead`` is (b, s), or (b,) where s is 1."""
    return x.transpose(0, 2, 1, 3).reshape(*lead, x.shape[1] * x.shape[3])


def _gather_table(cfg: TransformerConfig, pool: jax.Array, layer: int, block_tables: jax.Array) -> jax.Array:
    """Every token of every table column of one layer, the KV heads
    repeated: ``[b, n_heads, T * block_size, head_dim]``."""
    b, t = block_tables.shape
    rows = pool[layer, block_tables].reshape(b, t * pool.shape[2], cfg.kv_heads, -1)
    return _repeat_kv(rows.transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads)


def _embed_rows(params: Dict[str, Any], tokens: jax.Array, dtype: Any) -> jax.Array:
    """Embedding rows of ``tokens`` in the compute dtype: the rows first, then
    their conversion, so that the table is read where ``tokens`` point and not swept."""
    with jax.named_scope("serve.embed"):
        return jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(dtype)


def _head(cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array, row: Optional[int] = None) -> jax.Array:
    """Final norm and ``lm_head`` (tied: the embedding's transpose, times
    ``logit_scale``): float32 logits at every position of ``x``, or at ``row`` alone."""
    x = _norm_apply(cfg, x, params["ln_f"]["scale"])
    with jax.named_scope("serve.head"):
        x = x if row is None else x[:, row, :]
        if not cfg.tie_embeddings:
            return (x @ params["lm_head"]["kernel"].astype(cfg.dtype)).astype(jnp.float32)
        # float32 out of the product itself: the table is the head, and its logits are what a caller samples from
        logits = jnp.einsum(
            "...d,vd->...v", x, params["embed"]["embedding"].astype(cfg.dtype), preferred_element_type=jnp.float32
        )
        return logits * cfg.logit_scale


# The attention backends of the serving layer: ``attend(q, k, v, cache, i)`` with
# q [b, n_heads, s, head_dim], this call's own k, v [b, kv_heads, s, head_dim]
# and the pool that already holds them; returns [b, n_heads, s, head_dim].  An
# entry point picks one before its layer loop.


def _attend_local(q, k, v, cache, i):
    """Causal, over this call's own keys: the wide prefill's prompts start at position 0."""
    return reference_attention(q, k, v, causal=True)


def _attend_paged(cfg: TransformerConfig, block_tables: jax.Array, positions: jax.Array, window: Optional[int] = None):
    """One query a lane against the lane's live blocks, read where they lie
    in the pool (``ops/paged_attention.py``); ``positions`` [b], -1 = idle.
    ``window``: the layer slides, ``block_tables`` are the lanes' rings of the
    window store, and a lane reads its newest ``window`` tokens there."""
    pools = ("k", "v") if window is None else ("wk", "wv")

    def attend(q, k, v, cache, i):
        att = paged_decode_attention(
            q[:, :, 0, :], cache[pools[0]], cache[pools[1]], i, block_tables, positions, scale=cfg.head_dim ** -0.5,
            window=window,
        )
        return att.astype(cfg.dtype)[:, :, None, :]

    return attend


def _attend_chunk(cfg: TransformerConfig, block_tables: jax.Array, chunk: jax.Array, window: Optional[int] = None):
    """The prefill walk's read: the queries of chunk ``chunk`` (positions
    ``chunk * s ..``) against the keys up to the chunk's end, read from the
    pool a tile at a time (``ops/paged_attention.py paged_chunk_attention``);
    under ``window`` from the lanes' rings, and no key older than the window."""
    pools = ("k", "v") if window is None else ("wk", "wv")

    def attend(q, k, v, cache, i):
        b, h, s, d = q.shape
        att = paged_chunk_attention(
            q.reshape(b, cfg.kv_heads, h // cfg.kv_heads, s, d), cache[pools[0]], cache[pools[1]], i, block_tables, chunk,
            scale=cfg.head_dim ** -0.5, window=window,
        )
        return att.astype(cfg.dtype).reshape(b, h, s, d)

    return attend


def _masked_attention(cfg: TransformerConfig, q: jax.Array, keys: jax.Array, vals: jax.Array, mask: jax.Array) -> jax.Array:
    """Queries against gathered keys under ``mask`` (True = may see: ``[s, keys]``
    where the lanes are alike, else ``[b, s, keys]``) and a float32 softmax."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, keys, preferred_element_type=jnp.float32)
    logits = logits * cfg.head_dim ** -0.5
    seen = mask[None, None] if mask.ndim == 2 else mask[:, None]
    probs = jax.nn.softmax(jnp.where(seen, logits, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vals.dtype), vals)


def _attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every token of every table column, gathered from the
    pool, under ``mask``: decode without the paged path, the oracle that path
    is tested against."""

    def attend(q, k, v, cache, i):
        keys = _gather_table(cfg, cache["k"], i, block_tables)
        vals = _gather_table(cfg, cache["v"], i, block_tables)
        return _masked_attention(cfg, q, keys, vals, mask)

    return attend


def _attend_ring_table(cfg: TransformerConfig, positions: jax.Array):
    """A window layer's decode without the paged path: every lane's whole
    ring, gathered, each slot masked by the position it must hold.  Slot ``s``
    of a lane at position ``pos`` holds ``p = pos - (pos - s) % ring`` if it
    holds anything of this request; the query sees it if ``p >= 0`` and ``p >
    pos - window``.  What an earlier request left in the lane is never seen."""

    def attend(q, k, v, cache, i):
        b = q.shape[0]
        ring = cache["wk"].shape[1] // b * cache["wk"].shape[2]
        rows = lambda pool: _repeat_kv(  # noqa: E731
            pool[i].reshape(b, ring, cfg.kv_heads, -1).transpose(0, 2, 1, 3), cfg.n_heads // cfg.kv_heads
        )
        pos = positions[:, None]
        held = pos - (pos - jnp.arange(ring)[None, :]) % ring  # [b, ring]
        mask = (held >= 0) & (held > pos - cfg.sliding_window) & (pos >= 0)
        return _masked_attention(cfg, q, rows(cache["wk"]), rows(cache["wv"]), mask[:, None, :])

    return attend


# A retention layer's backends: ``retain(q, k, v, log_g, cache, j)`` with q [b,
# n_heads, s, head_dim], k, v [b, kv_heads, s, head_dim], the gate's logarithm
# [b, kv_heads, s] and ``j`` the layer's place in the state pool; returns ([b,
# n_heads, s, head_dim], the cache with the lanes' slots updated).  It is write
# and attend in one: the state is both.


def _retain_chunk(cfg: TransformerConfig, lanes: jax.Array, valid: jax.Array, fresh):
    """``s`` tokens a row after what the slots of ``lanes`` [b] hold (nothing,
    under ``fresh``: a sequence starts from a zeroed slot), and into them:
    the prefill walk's chunk, and the wide prefill as one chunk."""

    def retain(q, k, v, log_g, cache, j):
        state, norm = cache["rs"][j, lanes], cache["rz"][j, lanes]
        state, norm = jnp.where(fresh, 0.0, state), jnp.where(fresh, 0.0, norm)
        out, state, norm = retention_chunk(q, k, v, log_g, state, norm, valid)
        return out.astype(cfg.dtype), {**cache, "rs": cache["rs"].at[j, lanes].set(state), "rz": cache["rz"].at[j, lanes].set(norm)}

    return retain


def _retain_decode(cfg: TransformerConfig, live: jax.Array, impl: Optional[str] = None):
    """One token a lane, row ``b`` of the batch IS lane ``b``: the slot is
    decayed, takes the token and answers it (``ops/retention.py
    retention_decode``: the Pallas kernel on a TPU, in place); a lane that is
    not ``live`` [b] leaves its slot alone."""

    def retain(q, k, v, log_g, cache, j):
        out, state, norm = retention_decode(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0], cache["rs"], cache["rz"], j, live, impl=impl
        )
        return out.astype(cfg.dtype)[:, :, None, :], {**cache, "rs": state, "rz": norm}

    return retain


# Latent attention's backends: ``attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i)``
# with q_nope [b, n_heads, s, qk_nope], q_rope [b, n_heads, s, qk_rope] (after
# rope), this call's own latent rows c_kv [b, s, kv_lora] (after their norm) and
# k_r [b, s, qk_rope] (after rope), ``wkv_b`` [kv_lora, n_heads, qk_nope + v]
# and the pool that already holds the rows; returns [b, s, n_heads, v_head_dim].
# The first expands keys and values a head from the rows, as the equations are
# published; the other two stay in the latent space (``q_lat_h = q_nope_h
# W^K_h``, ``o_h = (sum p c_kv) W^V_h``: the same mathematics, and one row a
# token serves every head's scores and values).


def _latent_project(cfg, p, h, positions, rope):
    """A latent layer's projections of the normed input ``h`` [b, s, d]."""
    dt, r = cfg.dtype, cfg.kv_lora_rank
    c_q = _rms_apply(h @ p["wq_a"].astype(dt), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bhsk", c_q, p["wq_b"].astype(dt))
    kv = h @ p["wkv_a"].astype(dt)
    c_kv = _rms_apply(kv[..., :r], p["kv_norm"])
    k_r = _rope(kv[:, None, :, r:], positions, rope)[:, 0]
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], _rope(q[..., cfg.qk_nope_head_dim:], positions, rope)
    return q_nope, q_rope, c_kv, k_r


def _latent_attend_local(cfg: TransformerConfig):
    """Causal, over this call's own rows, keys and values expanded a head:
    the wide prefill (prompts start at position 0) and the training forward.
    On a TPU at a length worth tiling the flash kernel runs it, q, k and v
    padded with zeros to one width; no ``[heads, s, s]`` array is built."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        dt, nope = cfg.dtype, cfg.qk_nope_head_dim
        kv = jnp.einsum("bsc,chk->bhsk", c_kv, wkv_b.astype(dt))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None], kv.shape[:3] + k_r.shape[-1:])], axis=-1)
        v = kv[..., nope:]
        if _on_tpu() and q.shape[2] >= 256:
            from determined_tpu.ops.flash_attention import flash_attention

            width = -(-max(q.shape[-1], v.shape[-1]) // 128) * 128
            pad = lambda t: jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))  # noqa: E731
            out = flash_attention(pad(q), pad(k), pad(v), causal=True, scale=cfg.attn_scale)[..., : v.shape[-1]]
        else:
            out = reference_attention(q, k, v, causal=True, scale=cfg.attn_scale)
        return out.transpose(0, 2, 1, 3)

    return attend


def _on_tpu() -> bool:
    from determined_tpu.ops import paged_attention

    return paged_attention._on_tpu()  # one switch for the serving forward's kernels (tests steer it)


def _latent_split(cfg, wkv_b, q_nope):
    """(queries in the latent space [b, h, s, kv_lora], W^V [kv_lora, h, v])."""
    w = wkv_b.astype(cfg.dtype)
    return jnp.einsum("bhsn,chn->bhsc", q_nope, w[..., : cfg.qk_nope_head_dim]), w[..., cfg.qk_nope_head_dim:]


def _latent_attend_paged(cfg: TransformerConfig, block_tables: jax.Array, positions: jax.Array):
    """One query a lane against the lane's live latent rows, read where they
    lie in the pool (``ops/paged_attention.py``); ``positions`` [b], -1 = idle."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)[:, :, 0]
        q = jnp.pad(q, ((0, 0), (0, 0), (0, cache["kv"].shape[-1] - q.shape[-1])))
        with jax.named_scope("serve.mla.attend"):  # the kernel alone: what its roofline share times
            out = paged_latent_attention(
                q, cache["kv"], i, block_tables, positions, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
            )
        return jnp.einsum("bhc,chv->bhv", out.astype(cfg.dtype), w_v)[:, None]

    return attend


def _latent_attend_chunk(cfg: TransformerConfig, block_tables: jax.Array, chunk: jax.Array):
    """The prefill walk's read (``_attend_chunk``) in the latent space: every
    head's queries ``[q_lat | q_rope | zeros]`` against the pool's rows, whose
    first ``kv_lora_rank`` columns are the values."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, cache["kv"].shape[-1] - q.shape[-1]),))
        with jax.named_scope("serve.mla.attend"):
            out = paged_chunk_attention(
                q[:, None], cache["kv"], None, i, block_tables, chunk, scale=cfg.attn_scale, value_dim=cfg.kv_lora_rank
            )
        return jnp.einsum("bhsc,chv->bshv", out[:, 0].astype(cfg.dtype), w_v)

    return attend


def _latent_attend_table(cfg: TransformerConfig, block_tables: jax.Array, mask: jax.Array):
    """Queries against every row of every table column, gathered from the
    pool, under ``mask`` (as ``_attend_table``'s) and a float32 softmax:
    decode without the paged path, the oracle that path is tested against."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i):
        b, t = block_tables.shape
        r = cfg.kv_lora_rank
        rows = cache["kv"][i, block_tables].reshape(b, t * cache["kv"].shape[2], -1)
        lat, rot = rows[..., :r], rows[..., r: r + cfg.qk_rope_head_dim]
        q_lat, w_v = _latent_split(cfg, wkv_b, q_nope)
        logits = jnp.einsum("bhsc,bkc->bhsk", q_lat, lat, preferred_element_type=jnp.float32)
        logits = logits + jnp.einsum("bhsr,bkr->bhsk", q_rope, rot, preferred_element_type=jnp.float32)
        seen = mask[None, None] if mask.ndim == 2 else mask[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits * cfg.attn_scale, NEG_INF), axis=-1)
        out = jnp.einsum("bhsk,bkc->bhsc", probs.astype(lat.dtype), lat)
        return jnp.einsum("bhsc,chv->bshv", out, w_v)

    return attend


#: what a decode step of a model with expert layers counts beside its logits,
#: each summed over the expert layers: picks that landed on a held expert, and
#: held experts that got at least one row (whose matrices the step had to read)
SERVE_COUNTERS = ("serve.moe.held_picks", "serve.moe.experts_hit")
#: what a decode step of a model with window layers counts first: the cached
#: tokens its attention reads, summed over the lanes, in the full layers (the
#: context a layer) and in the window layers (the context or the window a layer)
SERVE_KV_COUNTERS = ("serve.kv.full_tokens", "serve.kv.window_tokens")
#: what a decode step of a model with retention layers counts first: the lanes
#: whose state it updated, and the bytes of state those hold over the retention
#: layers (lanes x layers x ``state_bytes_per_slot``): what the step had to read
SERVE_STATE_COUNTERS = ("serve.state.live_lanes", "serve.state.bytes")


def serve_counters(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of what ``transformer_decode(counters=True)`` counts, in the row's order."""
    return (
        (SERVE_STATE_COUNTERS if cfg.retention_layers else ()) + (SERVE_KV_COUNTERS if cfg.window_layers else ())
        + (SERVE_COUNTERS if cfg.moe_experts else ())
    )


def _serve_layer(cfg, i, blk, x, positions, write, attend, cache, live=None, sliding=None, retain=None):
    """Layer ``i`` of the serving forward, stated once under the three entry
    points below: norm, the attention's projections (q/k/v, or latent
    attention's), rope at ``positions`` ([s], or [b, s]), this call's rows into
    the pool at ``write`` = (physical block, slot), each [b, s] (or [b] where s
    is 1), then ``attend`` against the updated pool, so that a token sees its
    own key, then the output projection, the MLP or the experts held here
    (which count the tokens ``live`` [b, s] marks) and both residuals; under
    ``parallel_block`` the MLP or experts read the one norm attention read.
    A sliding-window layer writes and attends through ``sliding`` = (write,
    attend) instead, into the window store: its blocks are a lane's ring, and
    a block id past the store's end drops the row (idle lanes, padding).
    A power-retention layer writes no row and attends to none: ``retain``
    updates its lanes' slots of the state pool and answers from them (``write``
    may be None for a model of such layers alone).
    Returns (x, cache, what an expert layer counted or None)."""
    dt = cfg.dtype
    rope = cfg.rope(cfg.layer_type(i))
    slides, retains = cfg.layer_type(i) == SLIDING, cfg.layer_type(i) == RETENTION
    if not retains:
        phys, slots = sliding[0] if slides else write
    j = cfg.cache_index(i)
    h = _norm_apply(cfg, x, blk["ln1"]["scale"])
    if cfg.latent:
        with jax.named_scope("serve.mla"):
            p = blk["attn"]
            q_nope, q_rope, c_kv, k_r = _latent_project(cfg, p, h, positions, rope)
            with jax.named_scope("serve.kv.write"):
                row = jnp.concatenate([c_kv, k_r], axis=-1)
                row = jnp.pad(row, ((0, 0), (0, 0), (0, cache["kv"].shape[-1] - row.shape[-1])))
                cache = {"kv": cache["kv"].at[i, phys, slots].set(row.reshape(*phys.shape, -1))}
            att = attend(q_nope, q_rope, c_kv, k_r, p["wkv_b"], cache, i)
            x = x + jnp.einsum("bshv,hvD->bsD", att, p["wo"].astype(dt))
    elif retains:
        with jax.named_scope("serve.retention.qkvg"):
            q, k, v, log_g = _retention_proj(cfg, blk["attn"], h, positions)
        with jax.named_scope("serve.retention.state"):  # decay, update, query, normalise
            att, cache = retain(q, k, v, log_g, cache, j)
        with jax.named_scope("serve.retention.out"):
            x = x + jnp.einsum("bshk,hkD->bsD", att.transpose(0, 2, 1, 3), blk["attn"]["wo"]["kernel"].astype(dt))
    else:
        with jax.named_scope("serve.attn.qkv"):
            q, k, v = _attn_proj(blk["attn"], h, dt)
            q, k = _rope(q, positions, rope), _rope(k, positions, rope)
        with jax.named_scope("serve.kv.write"):
            if slides:
                cache = {
                    **cache,
                    "wk": cache["wk"].at[j, phys, slots].set(_pool_rows(k, phys.shape), mode="drop"),
                    "wv": cache["wv"].at[j, phys, slots].set(_pool_rows(v, phys.shape), mode="drop"),
                }
            else:
                cache = {
                    **cache,
                    "k": cache["k"].at[j, phys, slots].set(_pool_rows(k, phys.shape)),
                    "v": cache["v"].at[j, phys, slots].set(_pool_rows(v, phys.shape)),
                }
        with jax.named_scope("serve.attn.attend"):  # whichever backend the entry point picked
            if sliding is None:
                att = attend(q, k, v, cache, j)
            else:  # a cache of two kinds: the device trace tells them apart
                with jax.named_scope("serve.attn.window" if slides else "serve.attn.full"):
                    att = (sliding[1] if slides else attend)(q, k, v, cache, j)
            att = att.transpose(0, 2, 1, 3)  # [b, s, h, hd]
        with jax.named_scope("serve.attn.out"):
            x = x + jnp.einsum("bshk,hkD->bsD", att, blk["attn"]["wo"]["kernel"].astype(dt))
    if not cfg.parallel_block:  # else the one norm: what attention read
        h = _norm_apply(cfg, x, blk["ln2"]["scale"])
    if not cfg.use_moe(i):
        with jax.named_scope("serve.mlp"):
            return x + _mlp_apply(blk["mlp"], h, dt), cache, None
    from determined_tpu.models.moe import serve_routed_experts

    y, counted = serve_routed_experts(cfg, blk["moe"], h, live)
    return x + y, cache, counted


def _serve_layers(cfg, params, x, positions, write, attend, cache, live=None, sliding=None, retain=None):
    """Every layer; the last value is SERVE_COUNTERS' sums over the expert
    layers, [2] float32, or None for a model without them."""
    counted = []
    for i in range(cfg.n_layers):
        x, cache, c = _serve_layer(cfg, i, params[f"block_{i}"], x, positions, write, attend, cache, live, sliding, retain)
        if c is not None:
            counted.append(jnp.stack(c).astype(jnp.float32))
    return x, cache, sum(counted) if counted else None


def transformer_prefill(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, prompt_lens: jax.Array,
    block_tables: jax.Array, cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-prompt forward that also populates the paged cache: every
    position's logits in one pass over the padded width.  The engine runs
    :func:`transformer_prefill_chunked`, whose work follows the prompt; this
    form stays as the oracle the walk and the decode step are tested against.

    ``tokens`` [B, S] is the prompt padded to a fixed S (one trace);
    ``prompt_lens`` [B] the real lengths; ``block_tables`` [B, T] each
    lane's physical block ids.  Returns (logits [B, S, vocab] f32, cache).
    Logits at positions >= prompt_len are computed over padding — callers
    sample at ``prompt_len - 1``.  Causality makes positions < prompt_len
    match the full-sequence forward exactly (padding sits strictly after
    them), which is what the parity tests in tests/test_transformer.py pin.
    A model with sliding-window layers prefills through the walk alone (the
    window store is sized by the walk's chunk), its oracle the full forward.
    """
    _check_decodable(cfg)
    if cfg.window_layers:
        raise ValueError("the wide prefill runs full layers only: sliding-window layers prefill through transformer_prefill_chunked")
    block_size = _block_size(cache)
    b, s = tokens.shape
    x = _embed_rows(params, tokens, cfg.dtype)
    positions = jnp.arange(s)
    valid = positions[None, :] < prompt_lens[:, None]
    write = None
    if block_size is not None:
        with jax.named_scope("serve.kv.write"):
            # physical destination of every (lane, position): padded tail -> scratch
            phys = jnp.where(
                valid,
                jnp.take_along_axis(
                    block_tables, jnp.broadcast_to(positions[None, :] // block_size, (b, s)), axis=1
                ),
                0,
            )
            write = (phys, jnp.broadcast_to((positions % block_size)[None, :], (b, s)))
    attend = _latent_attend_local(cfg) if cfg.latent else _attend_local
    # a retention layer takes the prompt as one chunk into lane b's zeroed slot
    retain = _retain_chunk(cfg, jnp.arange(b), valid, True) if cfg.retention_layers else None
    # the padded tail takes no expert's rows
    live = valid if cfg.moe_experts else None
    x, cache, _ = _serve_layers(cfg, params, x, positions, write, attend, cache, live, retain=retain)
    return _head(cfg, params, x), cache


def transformer_decode(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
    block_tables: jax.Array, cache: Dict[str, jax.Array], *, chunk_blocks: int = 0,
    counters: bool = False, retention_impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the paged cache for every lane at once.

    ``tokens`` [B] the token each lane just consumed; ``positions`` [B] its
    global position (-1 marks an empty lane: it reads/writes the scratch
    block and its logits are garbage the caller ignores); ``block_tables``
    [B, T].  Returns (logits [B, vocab] f32, cache).  Shapes are lane-count
    static, so a mixed stream of request lengths never retraces — the
    continuous batcher joins and retires sequences by editing lane state,
    not by reshaping the batch.

    ``chunk_blocks`` > 0 selects the paged path
    (:func:`determined_tpu.ops.paged_attention.paged_decode_attention`):
    each lane's live blocks are read from the pool where it lies and folded
    into a float32 online softmax, by the Pallas kernel where the shapes
    tile on a TPU and by the same mathematics in ``jax.numpy`` elsewhere.
    The value only selects: the width of a pass is chosen from the shapes
    (it still has to divide the table width, as it always had to).  0 keeps
    the full-table gather ``[b, T*block_size, kv_heads, head_dim]`` every
    step, the oracle of the parity tests.  Both paths share every
    projection and the cache-write scatter, and agree to f32 tolerance.

    ``counters`` (the engine's decode program, where the model has expert
    or window layers): the logits come back ``[B + 1, vocab]``, and the last
    row's first entries are ``serve_counters(cfg)`` of this step, so that they
    reach the host in the logits' own copy.  Idle lanes take no expert's rows.

    Sliding-window layers read and write the window store (``init_kv_cache``):
    row ``b`` of the batch IS lane ``b``, whose ring holds the lane's newest
    tokens by position; a window layer reads ``min(position + 1, window)`` of
    them and nothing older, by the paged path over the ring (the gathered ring
    under ``chunk_blocks`` 0).

    Power-retention layers read and write the state pool alone: row ``b`` of
    the batch IS lane ``b`` and updates slot ``b``; an idle lane leaves its
    slot as it is.  A model of such layers alone has no pool, and
    ``block_tables`` is read by nothing.  ``retention_impl`` picks the form
    of ``ops/retention.py retention_decode`` (tests).
    """
    _check_decodable(cfg)
    block_size = _block_size(cache)
    t = block_tables.shape[1]
    if chunk_blocks and t % chunk_blocks:
        raise ValueError(f"chunk_blocks={chunk_blocks} must divide the table width {t}")
    active = positions >= 0
    pos = jnp.maximum(positions, 0)
    x = _embed_rows(params, tokens[:, None], cfg.dtype)
    retain = _retain_decode(cfg, active, retention_impl) if cfg.retention_layers else None
    if block_size is None:  # no layer reads a pool: nothing to write, no table to attend through
        x, cache, counted = _serve_layers(cfg, params, x, pos[:, None], None, None, cache, retain=retain)
        return _decode_result(cfg, params, x, active, pos, counted, counters), cache
    with jax.named_scope("serve.kv.write"):  # where each lane's row goes: idle lanes -> scratch
        phys = jnp.where(
            active, jnp.take_along_axis(block_tables, (pos // block_size)[:, None], axis=1)[:, 0], 0
        )
    if chunk_blocks:
        attend = (_latent_attend_paged if cfg.latent else _attend_paged)(cfg, block_tables, positions)
    else:
        # every cache position up to and including the current token
        with jax.named_scope("serve.attn.attend"):
            mask = (jnp.arange(t * block_size)[None, :] <= pos[:, None]) & active[:, None]  # [B, kv_len]
        attend = (_latent_attend_table if cfg.latent else _attend_table)(cfg, block_tables, mask[:, None, :])
    live = active[:, None] if cfg.moe_experts else None
    pos_col = pos[:, None]
    with jax.named_scope("serve.kv.write"):
        slots = pos % block_size
    sliding = None
    if cfg.window_layers:
        lanes, store = tokens.shape[0], cache["wk"].shape[1]
        ring_blocks = store // lanes
        with jax.named_scope("serve.kv.write"):  # lane b's ring; an idle lane's row is dropped
            first = jnp.arange(lanes, dtype=jnp.int32) * ring_blocks
            wphys = jnp.where(active, first + (pos // block_size) % ring_blocks, store)
        if chunk_blocks:
            rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
            sliding = ((wphys, slots), _attend_paged(cfg, rings, positions, cfg.sliding_window))
        else:
            sliding = ((wphys, slots), _attend_ring_table(cfg, positions))
    x, cache, counted = _serve_layers(cfg, params, x, pos_col, (phys, slots), attend, cache, live, sliding, retain)
    return _decode_result(cfg, params, x, active, pos, counted, counters), cache


def _decode_result(cfg, params, x, active, pos, counted, counters: bool) -> jax.Array:
    """A decode step's logits and, under ``counters``, the row of
    ``serve_counters(cfg)`` after them (``counted``: what the expert layers counted)."""
    logits = _head(cfg, params, x, row=0)
    if counters and cfg.window_layers:
        with jax.named_scope("serve.head"):  # the cached tokens this step's attention reads, by kind of layer
            lens = jnp.where(active, pos + 1, 0).astype(jnp.float32)
            n_window = len(cfg.window_layers)
            read = jnp.stack([
                jnp.sum(lens) * (cfg.n_layers - n_window), jnp.sum(jnp.minimum(lens, cfg.sliding_window)) * n_window,
            ])
            counted = read if counted is None else jnp.concatenate([read, counted])
    if counters and cfg.retention_layers:
        with jax.named_scope("serve.head"):  # the lanes whose state this step updated, and the bytes they hold
            lanes = jnp.sum(active.astype(jnp.float32))
            held = jnp.stack([lanes, lanes * (len(cfg.retention_layers) * state_bytes_per_slot(cfg))])
            counted = held if counted is None else jnp.concatenate([held, counted])
    if counters and counted is not None:
        with jax.named_scope("serve.head"):  # the counters ride in the logits' own copy
            row = jnp.zeros((1, logits.shape[1]), jnp.float32).at[0, : counted.shape[0]].set(counted)
            logits = jnp.concatenate([logits, row], axis=0)
    return logits


#: tokens an iteration of the prefill walk aims for.  An iteration sweeps every
#: weight once, so its time is the sweep's until the chunk's own arithmetic
#: passes it: at 256 tokens the sweep still bounds it at InternLM2's and at
#: DeepSeek-V3's widths (PERF.md section 5), and a prompt pays for at most 255
#: tokens it did not ask for.
PREFILL_CHUNK_TOKENS = 256


def prefill_chunk_tokens(block_size: int, prompt_tokens: int) -> int:
    """Tokens a chunk of :func:`transformer_prefill_chunked`, from the shapes:
    ``PREFILL_CHUNK_TOKENS`` in whole blocks and whole 128-wide tiles, or the
    longest prompt in whole blocks where that is shorter.  A caller pads its
    prompts to a multiple of it."""
    unit = math.lcm(block_size, 128)
    chunk = -(-PREFILL_CHUNK_TOKENS // unit) * unit
    return min(chunk, -(-prompt_tokens // block_size) * block_size)


def transformer_prefill_chunked(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array, start_lens: jax.Array,
    prompt_lens: jax.Array, block_tables: jax.Array, cache: Dict[str, jax.Array],
    lanes: Optional[jax.Array] = None, *, chunk_tokens: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill each prompt from ``start_lens`` on, a chunk of tokens at a time:
    the serving engine's one prefill program, for a cold prompt (``start`` 0)
    and for the un-cached suffix of one whose prefix is cached alike.

    ``tokens`` [B, S] is the FULL prompt padded to a multiple of the chunk
    (``prefill_chunk_tokens(block_size, S)``); ``start_lens`` [B] how many
    leading tokens already sit in cache blocks mapped into ``block_tables``
    (block-aligned by construction: only full blocks are shared);
    ``prompt_lens`` [B] the real lengths.  Returns (last_logits [B, vocab] f32,
    the logits at ``prompt_len - 1`` each lane samples its first token from,
    and the updated cache).

    The walk is one chunk of C tokens an iteration of a dynamic-trip-count
    ``fori_loop``, chunks at the absolute positions ``[c * C, (c + 1) * C)``
    for ``c`` in ``start // C .. ceil(len / C)``: the compute and the single
    compiled trace follow the tokens ASKED, not the padded width, and a
    70%-shared system prompt pays for its unique tail only.  An iteration
    reads every weight once for C tokens.  Queries attend against keys READ
    FROM THE CACHE up to the chunk's end (prefix blocks written by whoever
    prefilled them first, the chunk's own written just before attending),
    masked ``k_pos <= q_pos``, which makes a warm start and a cold ``start=0``
    run of the same prompt bitwise identical, wherever ``start`` falls in its
    chunk: the parity the prefix-cache admission tests pin.  Positions outside
    ``[start, len)`` write to scratch block 0 and take no expert's rows;
    since keys come from the cache rather than the local projection, garbage
    padding columns cannot leak into valid ones.  The head runs once, on the
    row of ``prompt_len - 1`` alone.

    Sliding-window layers keep their rows in the window store, in the ring of
    the decode lane each prompt will run in: ``lanes`` [B] (absent: row ``b`` is
    lane ``b``).  A chunk's rows go to the slots of their positions, and its
    queries read the ring back to ``window - 1`` positions before each of them:
    the ring is one chunk longer than the window, so no row a query of the
    chunk still sees is overwritten.  Such a prompt starts at 0 (a shared
    block holds no window state), and its walk computes every chunk.

    Power-retention layers carry a state through the walk, in the slot of the
    decode lane each prompt will run in (``lanes`` as above): the first chunk
    starts from a zeroed slot, every chunk is answered from the slot and folded
    into it, and the walk leaves it holding the prompt's state.  Such a prompt
    starts at 0 too (no block holds a state).  A model of such layers alone has
    no pool to take the block size from: ``chunk_tokens`` states the chunk.
    """
    _check_decodable(cfg)
    block_size = _block_size(cache)
    paged = block_size is not None
    b, s = tokens.shape
    if not paged:
        if chunk_tokens is None:
            raise ValueError("a cache without a pool states no block size: pass chunk_tokens")
        chunk, block_size = chunk_tokens, 1
    else:
        chunk = prefill_chunk_tokens(block_size, s)
    if s % chunk:
        raise ValueError(
            f"chunked prefill needs tokens padded to whole chunks (got S={s}, "
            f"chunk={chunk}, block_size={block_size})"
        )
    slot_lanes = jnp.arange(b, dtype=jnp.int32) if lanes is None else lanes.astype(jnp.int32)
    blocks, t = chunk // block_size, block_tables.shape[1]
    if cfg.window_layers:
        ring_blocks, store = window_ring_blocks(cfg, block_size, chunk), cache["wk"].shape[1]
        if store % ring_blocks:
            raise ValueError(
                f"the window store ({store} blocks) is not whole rings of {ring_blocks} blocks: it was sized "
                f"for another prefill chunk than {chunk} tokens"
            )
        with jax.named_scope("serve.kv.write"):
            first = (jnp.arange(b, dtype=jnp.int32) if lanes is None else lanes.astype(jnp.int32)) * ring_blocks
            rings = first[:, None] + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :]
    with jax.named_scope("serve.walk"):  # the trip count; the loop below is under it too, around its layers' scopes
        c_lo = jnp.min(start_lens) // chunk
        c_hi = (jnp.max(prompt_lens) + chunk - 1) // chunk
        offsets = jnp.arange(chunk)

    def body(c, carry):
        cache, last = carry
        with jax.named_scope("serve.embed"):
            toks = jax.lax.dynamic_slice(tokens, (0, c * chunk), (b, chunk))
        p = c * chunk + offsets  # absolute positions [chunk]
        with jax.named_scope("serve.kv.write"):
            valid = (p[None, :] >= start_lens[:, None]) & (p[None, :] < prompt_lens[:, None])  # [b, chunk]
        write = attend = None
        if paged:
            with jax.named_scope("serve.kv.write"):
                # a padded prompt may be wider than the table: those columns hold no valid row
                cols = jnp.minimum(c * blocks + offsets // block_size, t - 1)
                phys = jnp.where(valid, jnp.take(block_tables, cols, axis=1), 0)
                slots = jnp.broadcast_to((offsets % block_size)[None, :], (b, chunk))
            write = (phys, slots)
            attend = (_latent_attend_chunk if cfg.latent else _attend_chunk)(cfg, block_tables, c)
        retain = _retain_chunk(cfg, slot_lanes, valid, c == c_lo) if cfg.retention_layers else None
        sliding = None
        if cfg.window_layers:
            with jax.named_scope("serve.kv.write"):  # rows outside [start, len) are dropped
                wcols = (c * blocks + offsets // block_size) % ring_blocks
                wphys = jnp.where(valid, first[:, None] + wcols[None, :], store)
            sliding = ((wphys, slots), _attend_chunk(cfg, rings, c, cfg.sliding_window))
        # a leaf stored wider than the compute dtype is read as it lies and
        # converted on its way into each product, every iteration.  The
        # conversions depend on nothing the loop changes, and XLA would move
        # them before it: a second copy of the model in the compute dtype,
        # made and held for the whole call (3.3 GiB of scratch at InternLM2's
        # float32 leaves, and 11 ms before the first chunk).  Adding a zero
        # that only the loop's counter decides keeps them where they are.
        zero = (c < 0).astype(jnp.float32)
        layers = {name: sub for name, sub in params.items() if name.startswith("block_")}
        layers = jax.tree.map(lambda w: w if w.dtype == cfg.dtype else w + zero.astype(w.dtype), layers)
        x = _embed_rows(params, toks, cfg.dtype)
        x, cache, _ = _serve_layers(
            cfg, layers, x, p, write, attend, cache, valid if cfg.moe_experts else None, sliding, retain
        )
        with jax.named_scope("serve.head"):  # the one row the head will read
            sel = prompt_lens - 1 - c * chunk  # [b]
            row = jnp.take_along_axis(x, jnp.clip(sel, 0, chunk - 1)[:, None, None], axis=1)
            return cache, jnp.where(((sel >= 0) & (sel < chunk))[:, None, None], row, last)

    with jax.named_scope("serve.walk"):
        init = (cache, jnp.zeros((b, 1, cfg.d_model), cfg.dtype))
        cache, last = jax.lax.fori_loop(c_lo, c_hi, body, init)
    return _head(cfg, params, last, row=0), cache


#: latent attention's hparams: passed to the config as they are (absent: GQA)
_LATENT_HPARAMS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "softmax_scale")


class LMTrial(JaxTrial):
    """Language-model trial over synthetic (or user-supplied) token data.

    Hyperparameters: lr, global_batch_size, seq_len, vocab_size, d_model,
    n_layers, n_heads, n_kv_heads, d_ff, attention (auto/flash/ring/
    reference), remat, warmup_steps, dataset_size, pipe_microbatches;
    head_dim (where it is not d_model / n_heads), layer_types (one of
    full_attention / sliding_attention a layer) with sliding_window,
    rope_theta and rope_parameters (per layer type; YaRN); moe_experts with
    moe_every, and either moe_capacity_factor (top-2, capacity) or moe_top_k
    (dropless) with moe_intermediate_size and moe_experts_held = [first,
    count]; moe_aux_weight; norm (rms / layernorm) with norm_eps,
    parallel_block, tie_embeddings with logit_scale, moe_shared_combine.

    When the context mesh has a ``pipe`` axis of size P > 1, the trial
    restructures its params into stacked pipeline stages and trains through
    the GPipe schedule (``pipeline_forward``) — same init, same loss as
    pipe=1 (verified by ``tests/test_pipeline_e2e.py``).
    """

    def _pipe_stages(self) -> int:
        mesh = self.context.mesh
        return int(mesh.shape.get(MeshAxes.PIPELINE, 1)) if mesh is not None else 1

    def _pipe_microbatches(self, batch: int) -> int:
        m = self.context.get_hparam("pipe_microbatches", None)
        if m:
            return int(m)
        # default: 2 microbatches per stage (bubble fraction (P-1)/(M+P-1)),
        # shrunk to the largest divisor of the batch
        m = min(batch, 2 * self._pipe_stages())
        while batch % m:
            m -= 1
        return m

    def _pipe_schedule(self) -> Tuple[str, int]:
        """(schedule, virtual_stages) resolution: trial hparam override
        wins, else the experiment's ``optimizations`` knobs, else gpipe —
        the same precedence as ``_quant_mode``."""
        g = self.context.get_hparam
        opt = (
            self.context.exp_config.optimizations
            if self.context.exp_config is not None
            else None
        )
        name = g("pipeline_schedule", None)
        if name is None:
            name = opt.pipeline_schedule if opt is not None else "gpipe"
        v = g("virtual_stages", None)
        if v is None:
            v = opt.virtual_stages if opt is not None else 1
        return str(name), int(v)

    def pipeline_schedule_spec(self):
        """The trial's ``PipelineSchedule`` (None without a pipe axis) —
        the Trainer reads this for the jit-cache key and the goodput
        ledger's ``step.bubble`` analytic tick model."""
        pipe = self._pipe_stages()
        if pipe <= 1:
            return None
        from determined_tpu.parallel.pipeline import PipelineSchedule

        name, v = self._pipe_schedule()
        return PipelineSchedule(
            name=name,
            n_stages=pipe,
            num_microbatches=self._pipe_microbatches(
                self.context.get_global_batch_size()
            ),
            virtual_stages=v,
        )

    def _quant_mode(self) -> str:
        """quantized_matmul resolution: trial hparam override wins, else
        the experiment's ``optimizations.quantized_matmul`` knob, else
        off.  Platform-gated here (setup time) so fp8 on an unsupported
        chip fails with a clear InvalidExperimentConfig, not a lowering
        error mid-compile."""
        from determined_tpu.train._quant import require_platform

        mode = self.context.get_hparam("quantized_matmul", None)
        if mode is None and self.context.exp_config is not None:
            mode = self.context.exp_config.optimizations.quantized_matmul
        mode = str(mode) if mode else "none"
        require_platform(mode)
        return mode

    def _cfg(self) -> TransformerConfig:
        g = self.context.get_hparam
        pipe = self._pipe_stages()
        layer_types = g("layer_types", None)
        if pipe > 1 and bool(g("tie_embeddings", False)):
            raise ValueError("tie_embeddings: the embedding and the head sit on different pipeline stages")
        if pipe > 1 and (int(g("moe_experts", 0)) > 0 or layer_types):
            # MoE and layer types compose with pipe when every chunk sees the
            # same layer pattern: their periods must divide layers-per-chunk
            _, vstages = self._pipe_schedule()
            lps = int(g("n_layers", 2)) // (pipe * vstages)
            if int(g("moe_experts", 0)) > 0 and (lps == 0 or lps % int(g("moe_every", 2))):
                raise ValueError(
                    f"pipe={pipe} with MoE needs moe_every ({g('moe_every', 2)}) "
                    f"to divide layers-per-chunk ({lps})"
                )
            if layer_types and (lps == 0 or list(layer_types) != list(layer_types[:lps]) * (len(layer_types) // max(lps, 1))):
                raise ValueError(
                    f"pipe={pipe} needs the period of layer_types to divide "
                    f"layers-per-chunk ({lps}): layer j of every chunk is one stacked leaf"
                )
        mesh = self.context.mesh
        if int(g("moe_top_k", 0)) and pipe <= 1 and mesh is not None and mesh.size > 1:
            raise ValueError(
                "dropless experts (moe_top_k > 0) train on one device or inside "
                "pipeline stages (shard_map; each device of the `expert` axis holds "
                "its share): their grouped product is a Mosaic kernel, which GSPMD "
                f"cannot partition over a mesh of {mesh.size} devices"
            )
        held = g("moe_experts_held", None)
        return TransformerConfig(
            vocab_size=int(g("vocab_size", 2048)),
            d_model=int(g("d_model", 256)),
            n_layers=int(g("n_layers", 2)),
            n_heads=int(g("n_heads", 8)),
            n_kv_heads=g("n_kv_heads", None),
            d_ff=g("d_ff", None),
            max_seq_len=int(g("seq_len", 512)),
            attention_impl=str(g("attention", "auto")),
            remat=bool(g("remat", False)),
            dtype=jnp.bfloat16 if bool(g("bf16", True)) else jnp.float32,
            moe_experts=int(g("moe_experts", 0)),
            moe_every=int(g("moe_every", 2)),
            moe_capacity_factor=float(g("moe_capacity_factor", 1.25)),
            moe_aux_weight=float(g("moe_aux_weight", 0.01)),
            moe_top_k=int(g("moe_top_k", 0)),
            moe_intermediate_size=g("moe_intermediate_size", None),
            moe_experts_held=None if held is None else tuple(held),
            rope_theta=float(g("rope_theta", 10000.0)),
            head_dim=g("head_dim", None),
            layer_types=None if layer_types is None else tuple(layer_types),
            sliding_window=g("sliding_window", None),
            rope_parameters=g("rope_parameters", None),
            dense_prefix=int(g("dense_prefix", 0)),
            moe_router=str(g("moe_router", "softmax")),
            moe_n_group=int(g("moe_n_group", 1)),
            moe_topk_group=int(g("moe_topk_group", 1)),
            moe_routed_scaling=float(g("moe_routed_scaling", 1.0)),
            moe_shared_experts=int(g("moe_shared_experts", 0)),
            moe_shared_combine=str(g("moe_shared_combine", "sum")),
            norm=str(g("norm", "rms")),
            norm_eps=float(g("norm_eps", 1e-6)),
            parallel_block=bool(g("parallel_block", False)),
            tie_embeddings=bool(g("tie_embeddings", False)),
            logit_scale=float(g("logit_scale", 1.0)),
            **{k: g(k, None) for k in _LATENT_HPARAMS},
            quantized_matmul=self._quant_mode(),
        )

    #: step metrics the Trainer also pushes as tracer counters at each report
    #: (train/_trainer.py): what the dropless expert layers saw
    step_counters = (
        "moe.held_picks", "moe.picks", "moe.live_rows", "moe.expert_load_max", "moe.expert_load_mean",
        "moe_aux_loss",
    )

    @staticmethod
    def _apply(model: TransformerLM, params: Any, inputs: jax.Array, **kw: Any) -> Tuple[Any, Dict[str, jax.Array]]:
        """``model.apply`` and, where the model has dropless experts, a
        step's expert load from what the layers ``sow`` (no second forward):
        picks that landed on a held expert, all picks, the rows of the buffer
        the kernels touch (held picks and each group's padding to a tile), and
        the fullest held expert against the mean, over the layers."""
        if not model.cfg.moe_top_k:
            return model.apply(params, inputs, **kw), {}
        out, state = model.apply(params, inputs, mutable=["intermediates"], **kw)
        sown = jax.tree_util.tree_leaves_with_path(state["intermediates"])
        load, live_rows = (
            jnp.stack([x for path, x in sown if name in jax.tree_util.keystr(path)]).astype(jnp.float32)
            for name in ("load", "live_rows")
        )
        picks = load.shape[0] * inputs.size * model.cfg.moe_top_k
        return out, {
            "moe.held_picks": jnp.sum(load),
            "moe.picks": jnp.asarray(picks, jnp.float32),
            "moe.live_rows": jnp.sum(live_rows),
            "moe.expert_load_max": jnp.max(load),
            "moe.expert_load_mean": jnp.mean(load),
        }

    @property
    def tokens_per_sample(self) -> int:
        """Tokens one sample contributes per step — the goodput ledger's
        tokens/s denominator (observability/_goodput.py)."""
        return int(self.context.get_hparam("seq_len", 512))

    @property
    def flops_per_token(self) -> float:
        """Fwd+bwd matmul FLOPs per token by the standard 6N + attention
        convention (``benchmark/benchlib/costs.py`` counts the same), for the ledger's MFU
        estimate: N counts the projections at the stated head_dim, a gated
        MLP or a token's active experts (its expected picks among the held
        ones) with the router, and the head; attention counts the keys a
        layer's queries can see (the window of a sliding layer)."""
        cfg = self._cfg()
        d, width = cfg.d_model, cfg.n_heads * cfg.head_dim
        attn = d * cfg.head_dim * (2 * cfg.n_heads + 2 * cfg.kv_heads)
        if cfg.latent:
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            attn = d * (cfg.q_lora_rank + cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.n_heads * (
                cfg.q_lora_rank * qk + cfg.kv_lora_rank * (cfg.qk_nope_head_dim + cfg.v_head_dim) + cfg.v_head_dim * d
            )
            width = cfg.n_heads * (qk + cfg.v_head_dim) // 2
        n_params, seen = cfg.vocab_size * d, 0
        for i in range(cfg.n_layers):
            n_params += attn
            if cfg.use_moe(i):
                held = (cfg.moe_experts_held or (0, cfg.moe_experts))[1]
                active = cfg.moe_top_k * held / cfg.moe_experts if cfg.moe_top_k else 2
                n_params += d * cfg.moe_experts + active * 3 * d * (cfg.moe_intermediate_size or cfg.ff_dim)
            else:
                n_params += 3 * d * cfg.ff_dim
            seen += min(cfg.window(cfg.layer_type(i)) or cfg.max_seq_len, cfg.max_seq_len)
        return float(6 * n_params + 12 * seen * width)

    def build_model(self) -> TransformerLM:
        return TransformerLM(self._cfg(), mesh=self.context.mesh)

    def build_optimizer(self) -> optax.GradientTransformation:
        g = self.context.get_hparam
        lr = float(g("lr", 3e-4))
        warmup = int(g("warmup_steps", 100))
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, lr, warmup, int(g("decay_steps", 10000))
        )
        self.lr_schedule = schedule  # surfaced as the per-batch `lr` metric
        # adam first-moment dtype: bf16 halves its HBM traffic (the
        # optimizer update is bandwidth-bound); second moment stays f32
        # for the rsqrt's dynamic range
        mu_dtype = jnp.bfloat16 if bool(g("adam_mu_bf16", False)) else None
        fused = g("fused_adamw", "auto")
        if fused == "auto":
            fused = jax.default_backend() == "tpu"
        if fused:
            # single-sweep Pallas AdamW+clip (ops/fused_adamw.py): 8 HBM
            # passes vs optax's measured 9 on the bandwidth-bound update
            from determined_tpu.ops.fused_adamw import fused_adamw

            return fused_adamw(
                schedule,
                weight_decay=float(g("weight_decay", 0.01)),
                clip_norm=float(g("grad_clip", 1.0)),
                mu_dtype=mu_dtype,
            )
        clip = optax.clip_by_global_norm(float(g("grad_clip", 1.0)))

        def clip_update(updates, state, params=None):
            with jax.named_scope("optim.clip"):  # as fused_adamw names its own norm and scale
                return clip.update(updates, state, params)

        return optax.chain(
            optax.GradientTransformation(clip.init, clip_update),
            optax.adamw(
                schedule,
                weight_decay=float(g("weight_decay", 0.01)),
                mu_dtype=mu_dtype,
            ),
        )

    def _dataset(self, seed: int) -> SyntheticDataset:
        g = self.context.get_hparam
        seq = int(g("seq_len", 512))
        size = int(g("dataset_size", 2048))
        return SyntheticDataset(
            {"tokens": ((seq + 1,), np.int32, int(g("vocab_size", 2048)))},
            size=size,
            seed=seed,
        )

    def build_training_data_loader(self) -> DataLoader:
        return DataLoader(
            self._dataset(0),
            self.context.get_global_batch_size(),
            shuffle=True,
            seed=self.context.seed,
        )

    def build_validation_data_loader(self) -> DataLoader:
        return DataLoader(
            self._dataset(1),
            self.context.get_global_batch_size(),
            shuffle=False,
            seed=self.context.seed,
        )

    def model_inputs(self, batch: Dict[str, Any]) -> Tuple[Any, ...]:
        return (jnp.asarray(batch["tokens"])[:, :-1],)

    def restructure_params(self, params: Any) -> Any:
        # pipe > 1: restack per-layer blocks into pipeline stages
        pipe = self._pipe_stages()
        if pipe > 1:
            _, vstages = self._pipe_schedule()
            return split_pipeline_params(params, pipe, vstages)
        return params

    def param_logical_specs(self, params: Any) -> Any:
        if self._pipe_stages() <= 1:
            return None
        from flax.core import meta as flax_meta

        from determined_tpu.train._trainer import _specs_from_flax_metadata

        outer = _specs_from_flax_metadata(params["outer"])
        if outer is None:
            outer = jax.tree.map(lambda _: None, flax_meta.unbox(params["outer"]))
        from determined_tpu.parallel.pipeline import _path_has_expert_leaf

        _, vstages = self._pipe_schedule()
        # interleaved leaves lead [stage, virtual, ...]; the virtual-stage
        # dim stays unsharded (each rank owns all V of its chunks)
        head = ("stage", None) if vstages > 1 else ("stage",)

        def block_spec(path, a):
            if _path_has_expert_leaf(path):
                return head + ("expert",) + (None,) * (a.ndim - len(head) - 1)
            return head + (None,) * (a.ndim - len(head))

        blocks = jax.tree_util.tree_map_with_path(block_spec, params["blocks"])
        return {"outer": outer, "blocks": blocks}

    def loss(
        self, model: TransformerLM, params: Any, batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        tokens = batch["tokens"]
        with jax.named_scope("lm.embed"):
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
        g = self.context.get_hparam
        fused = g("fused_ce", "auto")
        if fused == "auto":
            fused = model.cfg.vocab_size >= 8192
        if self._pipe_stages() > 1:
            return self._pipeline_loss(model, params, inputs, targets, fused)
        if fused:
            from flax.core import meta as flax_meta

            from determined_tpu.ops.cross_entropy import fused_cross_entropy

            (hidden, moe_aux), moe_load = self._apply(
                model, params, inputs, return_hidden=True, return_aux=True
            )
            if model.cfg.tie_embeddings:
                kernel = flax_meta.unbox(params["params"]["embed"]["embedding"]).T * model.cfg.logit_scale
            else:
                kernel = flax_meta.unbox(params["params"]["lm_head"]["kernel"])
            chunk = g("ce_chunk", None)
            shards = self.context.batch_axis_size if self.context.mesh is not None else 1
            loss = fused_cross_entropy(
                hidden,
                kernel,
                targets,
                chunk_size=None if chunk in (None, "auto") else int(chunk),
                compute_dtype=model.cfg.dtype,
                batch_shards=shards,
                bf16_residual=bool(g("ce_bf16_residual", False)),
            )
        else:
            (logits, moe_aux), moe_load = self._apply(model, params, inputs, return_aux=True)
            with jax.named_scope("loss.ce"):
                loss = optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()
        with jax.named_scope("loss.ce"):
            metrics = {"perplexity": jnp.exp(loss), **moe_load}
            if model.cfg.moe_experts > 0:
                metrics["moe_aux_loss"] = moe_aux
                loss = loss + model.cfg.moe_aux_weight * moe_aux
        return loss, metrics

    def _pipeline_loss(
        self,
        model: TransformerLM,
        params: Any,
        inputs: jax.Array,
        targets: jax.Array,
        fused: bool,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Loss through the configured microbatch schedule (mesh has a
        pipe axis > 1)."""
        g = self.context.get_hparam
        mb = self._pipe_microbatches(inputs.shape[0])
        sched, vstages = self._pipe_schedule()
        if fused:
            from flax.core import meta as flax_meta

            from determined_tpu.ops.cross_entropy import fused_cross_entropy

            hidden, moe_aux = pipeline_forward(
                model.cfg, self.context.mesh, params, inputs, mb,
                return_hidden=True, rules=self.context.rules, return_aux=True,
                schedule=sched, virtual_stages=vstages,
            )
            kernel = flax_meta.unbox(params["outer"]["params"]["lm_head"]["kernel"])
            chunk = g("ce_chunk", None)
            shards = self.context.batch_axis_size
            loss = fused_cross_entropy(
                hidden,
                kernel,
                targets,
                chunk_size=None if chunk in (None, "auto") else int(chunk),
                compute_dtype=model.cfg.dtype,
                batch_shards=shards,
                bf16_residual=bool(g("ce_bf16_residual", False)),
            )
        else:
            logits, moe_aux = pipeline_forward(
                model.cfg, self.context.mesh, params, inputs, mb,
                rules=self.context.rules, return_aux=True,
                schedule=sched, virtual_stages=vstages,
            )
            with jax.named_scope("loss.ce"):
                loss = optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()
        with jax.named_scope("loss.ce"):
            metrics = {"perplexity": jnp.exp(loss)}
            if model.cfg.moe_experts > 0:
                metrics["moe_aux_loss"] = moe_aux
                loss = loss + model.cfg.moe_aux_weight * moe_aux
        return loss, metrics

    def evaluate_batch(
        self, model: TransformerLM, params: Any, batch: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        loss, metrics = self.loss(model, params, batch, jax.random.key(0))
        return {"validation_loss": loss, "validation_perplexity": metrics["perplexity"]}
