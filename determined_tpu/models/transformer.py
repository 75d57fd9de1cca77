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
- a type per layer: full causal attention, a sliding window, power
  retention (``ops/retention.py``: gated attention of degree 2, whose serving
  cache is a fixed-size state a lane and not keys and values a token), or
  attention heads beside Mamba-2 heads under one norm (``ops/ssm.py``:
  Falcon-H1's block, whose serving cache is K and V a token AND a state a lane),
  or attention inside a compressed, convolved latent (``CompressedAttention``:
  ZAYA1's CCA; trained, not served yet);
- experts: the top-2 capacity layer or dropless top-k over the experts a
  device holds (models/moe.py); under the "mlp" router a layer hands the next
  its router's state, a second value beside the residual stream;
- ``residual_scaling``: a learned scale and bias on both terms of each merge;
- ``shortcut_block``: two attention sublayers and two dense MLPs a block round
  an expert branch that joins the stream after the second MLP (LongCat-Flash's
  double layer), with identity experts behind a biased softmax router;
- ``mixer_block``: a layer is ONE norm and ONE mixer, a Mamba-2 mixer, attention
  or the experts alone (Nemotron-H's block); its experts may be two-matrix
  squared-ReLU ones that work in a latent between two shared projections;
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
from determined_tpu.ops import kernel_form
from determined_tpu.ops.attention import NEG_INF, dot_product_attention, reference_attention
from determined_tpu.ops.gated_delta import DECAY_FLOOR, gdn_chunk, l2_heads, state_shape as gdn_pool_shape
from determined_tpu.ops.paged_attention import index_scores, index_topk_mask
from determined_tpu.ops.retention import recent_shapes, retention_quadratic, state_shapes
from determined_tpu.ops.ssm import ssm_scan, state_shape as ssm_pool_shape
from determined_tpu.ops.ring_attention import ring_attention
from determined_tpu.parallel.mesh import MeshAxes
from determined_tpu.parallel.sharding import with_sharding_constraint
from determined_tpu.train._trial import JaxTrial


#: the kinds of layer, under the names published configurations give them
FULL, SLIDING, RETENTION = "full_attention", "sliding_attention", "power_retention"
#: full attention and a Mamba-2 mixer side by side: ``x + a(u) + s(u)`` for ONE norm ``u`` (Falcon-H1's block)
HYBRID = "attention_mamba2"
#: compressed convolutional attention: full causal attention over q and k that two causal
#: convolutions mixed over time inside their latent (``CompressedAttention``)
CCA = "cca"
#: under ``mixer_block`` a layer is ONE mixer (Nemotron-H's block): a Mamba-2 mixer alone, the dropless experts
#: alone, or (``full_attention``) attention alone
MAMBA2, EXPERTS = "mamba2", "experts"
#: a Gated-DeltaNet mixer in attention's place (``GatedDeltaNet``; the published ``layer_types`` value): a
#: delta-rule state a value head, no K and V a token
LINEAR = "linear_attention"
LAYER_TYPES = (FULL, SLIDING, RETENTION, HYBRID, CCA, MAMBA2, EXPERTS, LINEAR)
#: what ``indexer_types`` says of a layer: it holds an indexer and attends over the keys that picks, or it
#: holds none and attends over the picks of the nearest ``full`` layer before it
INDEX_FULL, INDEX_SHARED = "full", "shared"
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
    # head_dim each shared by the heads (Qwen3's): power_retention layers run
    # it, or GQA's attention layers do (``Attention``), in a model of no
    # retention layer.  attn_output_gate (Qwen3-Next's gated attention): ``wq``
    # is twice as wide, a head's ``[query | gate]``, and what the head attends
    # to is multiplied by ``sigmoid(gate)`` before ``wo``; on LATENT attention
    # (Ling-3.0's) the gate is ONE value a head from a projection of its own
    # (the leaf ``w_gate`` [d_model, n_heads]), times the head's whole output
    qk_norm: bool = False
    attn_output_gate: bool = False
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
    # "mlp" (ZAYA1's; models/moe.py ``route_mlp``): top-1 by an MLP over
    # router_hidden_size values a token to which the layer before's are added
    # (a state every expert block hands the next), the pick's weight its
    # probability as it is, a selection bias that picks and never weighs
    moe_router: str = "softmax"
    router_hidden_size: Optional[int] = None
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scaling: float = 1.0
    moe_shared_experts: int = 0
    # moe_shared_gate: what the shared experts add is multiplied by ``sigmoid(x . w_g)``, a scalar a token
    # from one learned vector of d_model (the leaf ``shared_gate``; Qwen3-Next's)
    moe_shared_gate: bool = False
    # "sigmoid" is the plain form of the grouped router: sigmoid scores, the
    # top-k largest, weights normalised to one; no groups, no bias.  How the
    # shared experts' outputs combine: "sum", or "mean" (their sum over their
    # number)
    moe_shared_combine: str = "sum"
    # "softmax_bias" (LongCat-Flash's): softmax scores over moe_experts +
    # moe_zero_experts outputs, the top-k of scores + a selection bias (which
    # picks and never weighs), weights the picks' scores times
    # moe_routed_scaling, NOT renormalised.  The last moe_zero_experts outputs
    # are identity experts: a pick there adds ``w x`` and costs no row of any
    # buffer (``moe_experts`` stays the count of real experts, the range
    # ``moe_experts_held`` lies in)
    moe_zero_experts: int = 0
    # An expert's form (the dropless layer's routed and shared experts alike): "swiglu", three matrices, ``W_down
    # (silu(W_gate x) * W_up x)``; "relu2", TWO, ``W_down relu(W_up x)^2`` (no ``w_gate`` leaf).
    # moe_latent_size: the routed experts work in a latent of that width between two projections all of them
    # share (leaves ``w_latent_in`` [d_model, latent] and ``w_latent_out`` [latent, d_model], no bias):
    # ``(sum over held picks of w_e Expert_e(x W_in)) W_out``; the router and the shared experts read the
    # full-width ``x``.  moe_shared_intermediate_size: the width of all shared experts together (None:
    # moe_shared_experts x an expert's)
    moe_expert_act: str = "swiglu"
    moe_latent_size: Optional[int] = None
    moe_shared_intermediate_size: Optional[int] = None
    # The block's norms: "rms" (no mean taken) or "layernorm" (mean and variance
    # over the features in float32, a weight and no bias), with norm_eps.
    # parallel_block: ONE norm a block, attention and the MLP or experts both
    # read it, ``x + Attn(LN(x)) + FFN(LN(x))``.  tie_embeddings: no lm_head,
    # the logits are the final norm times the embedding's transpose, times
    # logit_scale.
    # residual_scaling: both merges of a sequential block are ``(x + br) * ar
    # + (f + bf) * af`` with four learned vectors of d_model each (scales one,
    # biases zero at the start) in place of ``x + f``.
    # shortcut_block (LongCat-Flash's shortcut-connected experts): TWO attention
    # sublayers and two dense MLPs a block, each with its own weights and norms
    # (``attn``, ``mlp``, ``ln1``, ``ln2`` and ``attn_1``, ``mlp_1``, ``ln1_1``,
    # ``ln2_1``), and every block's experts read the FIRST sublayer's second norm
    # and join the stream after the second MLP: ``a0 = x + Attn0(LN(x)); u =
    # LN(a0); m = Experts(u); b0 = a0 + MLP0(u); a1 = b0 + Attn1(LN(b0)); b1 = a1
    # + MLP1(LN(a1)); out = b1 + m``.  Serving keeps two rows a token a block.
    # mixer_block (Nemotron-H's block): a layer is ONE norm (``ln1``), ONE mixer and one residual, ``x +
    # Mixer(LN(x))``, the mixer by the layer's type, a tuple a layer: ``full_attention`` (attention alone, subtree
    # ``attn``), ``mamba2`` (a Mamba-2 mixer alone, ``ssm``) or ``experts`` (the dropless experts alone, ``moe``:
    # such a layer keeps nothing of the past).  ``layer_types`` says which layers hold experts (``moe_every`` and
    # ``dense_prefix`` say nothing here) and no layer has a dense MLP.
    norm: str = "rms"
    norm_eps: float = 1e-6
    parallel_block: bool = False
    residual_scaling: bool = False
    shortcut_block: bool = False
    mixer_block: bool = False
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    # Latent attention (MLA): kv_lora_rank set swaps every block's GQA for
    # it: every full_attention layer's, the other types keeping their own
    # mixers (a linear_attention layer beside it keeps a state a lane while
    # the full layers keep a latent row a token).  Queries come through a
    # q_lora_rank bottleneck (None: straight from the stream, ONE matrix
    # ``wq`` [d_model, n_heads, qk]) as n_heads heads of
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
    # constants on the two normed latents, before their up-projections (the
    # cached row holds the scaled one): LongCat-Flash's (d_model / rank) ** 0.5
    q_latent_scale: float = 1.0
    kv_latent_scale: float = 1.0
    # Learned sparse selection of the keys latent attention reads (DeepSeek
    # Sparse Attention's lightning indexer; GLM-5.2 shares a layer's picks with
    # the layers after it).  indexer_types states a layer as layer_types does:
    # a "full" layer holds an indexer (index_n_heads heads of index_head_dim on
    # the normed query latent, ONE key of index_head_dim a token from the
    # layer's normed input under a LayerNorm with a bias, rotary on the first
    # qk_rope_head_dim of both, a weight a head from the normed input) and
    # attends, query by query, over the index_topk earlier tokens it scores
    # highest (``_index_project``, ``ops/paged_attention.py index_scores``); a
    # "shared" layer holds none and attends over the picks of the nearest full
    # layer before it.  None: every layer attends over all earlier tokens.
    # Serving caches a full layer's index key a token beside the latent row
    indexer_types: Optional[Tuple[str, ...]] = None
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # A cca layer (``CompressedAttention``): q and k pass a depthwise causal
    # convolution over cca_time0 tokens and one over cca_time1 tokens that
    # mixes each head's channels; rotary turns the first partial_rotary_factor
    # of every head (1 turns the whole head), in cca layers and in GQA's
    # attention layers (``Attention``: full, sliding, beside a Mamba-2 mixer)
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 1.0
    # An attention_mamba2 layer's Mamba-2 mixer (``Mamba2`` below): ssm_heads
    # heads of ssm_head_dim over ssm_groups groups that share B and C of
    # ssm_state values, a causal depthwise convolution over ssm_conv tokens, and
    # ssm_chunk tokens a chunk of the whole-sequence form
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # A linear_attention layer's Gated-DeltaNet mixer (``GatedDeltaNet`` below): linear_value_heads value heads
    # of linear_value_head_dim, each with a delta-rule state of linear_key_head_dim x linear_value_head_dim;
    # linear_key_heads heads of q and k, each serving linear_value_heads / linear_key_heads consecutive value
    # heads; a causal depthwise convolution over linear_conv tokens on q, k and v (no bias), and linear_chunk
    # tokens a sub-chunk of the chunked form (``ops/gated_delta.py``).  linear_decay_floor None: Gated DeltaNet's
    # decay, ONE a value head a token, ``g = -exp(A_log) softplus(a + dt_bias)``, and an output gate ``silu(z)``.
    # Set (Kimi Delta Attention's bounded gate, ``kda_lower_bound``, as Ling-3.0 runs it): a decay a CHANNEL of the
    # key from a full projection of its own (``w_decay`` [d_model, value heads x K], ``dt_bias`` as wide), ``g =
    # floor * sigmoid(exp(A_log) * (a + dt_bias))`` in (floor, 0), and an output gate ``sigmoid(z)``
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv: int = 4
    linear_chunk: int = 64
    linear_decay_floor: Optional[float] = None
    # muP's scalars, constants of the forward and no leaves (1: not there):
    # on the embedding's rows; on k, on attention's input and output; on the
    # Mamba-2 mixer's input, on the five segments z, x, B, C, dt of its
    # in-projection and on its output; on the MLP's gate and output.  The
    # head's is ``logit_scale``
    embedding_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
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
        setattr_("ssm_multipliers", tuple(float(m) for m in self.ssm_multipliers))
        setattr_("mlp_multipliers", tuple(float(m) for m in self.mlp_multipliers))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has one scalar for each of z, x, B, C, dt and mlp_multipliers two")
        types = set(self.layer_types or (FULL,))
        if self.mixer_block:
            refused = [
                what for what, there in (
                    ("parallel_block", self.parallel_block), ("shortcut_block", self.shortcut_block),
                    ("residual_scaling", self.residual_scaling), ("latent attention (kv_lora_rank)", self.latent),
                    ("pipeline stages (a `seq` or `expert` axis)", self.seq_axis_name is not None or self.expert_axis_name is not None),
                    ("a layer type other than full_attention, mamba2 or experts (a linear_attention layer sits in a "
                     "sequential block, before its experts)", not types <= {FULL, MAMBA2, EXPERTS}),
                    ("moe_router mlp", self.moe_router == "mlp"),
                ) if there
            ]
            if refused:
                raise ValueError(
                    "mixer_block is one norm and ONE mixer a layer (full_attention, mamba2 or experts, by layer_types); "
                    "it does not run with " + ", ".join(refused)
                )
            if (EXPERTS in types) != (self.moe_experts > 0) or (EXPERTS in types and not self.moe_top_k):
                raise ValueError(
                    "mixer_block: the experts layers are those layer_types names `experts`, and they are dropless "
                    f"(moe_experts > 0 and moe_top_k > 0 with them, moe_experts 0 without; got {self.moe_experts} experts, "
                    f"top-{self.moe_top_k}, layer_types {self.layer_types})"
                )
        elif types & {MAMBA2, EXPERTS}:
            raise ValueError("a mamba2 or an experts layer is a layer of ONE mixer: it belongs to mixer_block")
        if self.ssm_layers and (
            min(self.ssm_heads, self.ssm_head_dim, self.ssm_state, self.ssm_groups) < 1 or self.ssm_conv < 2
            or self.ssm_heads % self.ssm_groups or self.latent or self.parallel_block or self.seq_axis_name is not None
        ):
            raise ValueError(
                "an attention_mamba2 layer needs ssm_heads (whole groups of them), ssm_head_dim, ssm_state and "
                "ssm_conv >= 2, in a sequential block, without latent attention or a `seq` axis (a mamba2 layer likewise)"
            )
        if self.linear_layers and (
            min(self.linear_key_heads, self.linear_value_heads, self.linear_key_head_dim, self.linear_value_head_dim, self.linear_chunk) < 1
            or self.linear_conv < 2 or self.linear_value_heads % self.linear_key_heads
            or self.parallel_block or self.shortcut_block or self.seq_axis_name is not None or self.indexer_types is not None
        ):
            raise ValueError(
                "a linear_attention layer needs linear_value_heads (whole groups a linear_key_heads head), linear_key_head_dim, "
                "linear_value_head_dim, linear_chunk >= 1 and linear_conv >= 2, in a sequential block: it does not run under "
                "parallel_block or shortcut_block, beside an indexer (indexer_types: every layer would keep a latent row) or "
                "under a `seq` axis (its state is carried along the sequence)"
            )
        if self.linear_decay_floor is not None and not (self.linear_layers and DECAY_FLOOR <= self.linear_decay_floor < 0):
            raise ValueError(
                "linear_decay_floor (a decay a channel under the bounded gate) belongs to linear_attention layers and lies in "
                f"[{DECAY_FLOOR}, 0): the chunked form's two-sided scaling holds no smaller one inside float32 (got {self.linear_decay_floor})"
            )
        if CCA in (self.layer_types or ()) and (
            self.latent or self.parallel_block or self.seq_axis_name is not None or self.n_heads % self.kv_heads
            or self.kv_heads % 2 or min(self.cca_time0, self.cca_time1) < 1
        ):
            raise ValueError(
                "a cca layer needs whole groups of query heads a KV head, an even count of KV heads (half take the "
                "token before's value) and cca_time0, cca_time1 >= 1, in a sequential block, without latent "
                "attention or a `seq` axis"
            )
        rotary = self.head_dim * self.partial_rotary_factor
        if not 0 < self.partial_rotary_factor <= 1 or rotary != int(rotary) or int(rotary) % 2:
            raise ValueError(f"partial_rotary_factor={self.partial_rotary_factor} must leave an even count of head_dim={self.head_dim} to turn")
        if self.partial_rotary_factor != 1 and (self.latent or self.retention_layers):
            raise ValueError(
                "partial_rotary_factor < 1 runs in cca layers and in GQA's attention layers: not with latent attention "
                "(kv_lora_rank), whose rotary part is its own width, nor in a power_retention layer"
            )
        if self.residual_scaling and (self.parallel_block or self.expert_axis_name is not None):
            raise ValueError("residual_scaling runs in a sequential block, outside pipeline stages")
        if self.qk_norm and (self.latent or CCA in types or 0 < len(self.retention_layers) < self.n_layers):
            raise ValueError(
                "qk_norm runs in power_retention layers only (every layer must be one), or in GQA's attention layers in a "
                "model of no retention layer: not with latent attention (kv_lora_rank) or a cca layer"
            )
        if self.attn_output_gate and (types & {RETENTION, CCA} or (self.latent and self.indexer_types is not None)):
            raise ValueError(
                "attn_output_gate gates GQA's attention layers (a gate a value) or latent attention's (a gate a head): not a "
                "power_retention or a cca layer, nor latent attention under an indexer"
            )
        if self.moe_shared_gate and not self.moe_shared_experts:
            raise ValueError("moe_shared_gate gates the shared experts: it belongs to moe_shared_experts")
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
            if not 1 <= self.moe_top_k <= self.moe_experts + self.moe_zero_experts:
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
        if self.moe_router not in ("softmax", "softmax_bias", "sigmoid", "sigmoid_grouped", "mlp"):
            raise ValueError(f"moe_router is softmax, softmax_bias, sigmoid, sigmoid_grouped or mlp (got {self.moe_router!r})")
        if self.moe_zero_experts < 0 or (self.moe_zero_experts and self.moe_router != "softmax_bias"):
            raise ValueError("moe_zero_experts (identity experts after the real ones) belong to moe_router softmax_bias")
        if self.shortcut_block and (
            not self.moe_top_k or self.moe_every != 1 or self.dense_prefix or self.parallel_block or self.residual_scaling
            or self.moe_router == "mlp" or set(self.layer_types or (FULL,)) != {FULL}
            or self.seq_axis_name is not None or self.expert_axis_name is not None
        ):
            raise ValueError(
                "shortcut_block runs dropless experts in EVERY block (moe_top_k > 0, moe_every 1, no dense_prefix) of "
                "full-attention layers, without parallel_block, residual_scaling or moe_router mlp, and outside pipeline "
                "stages (a `seq` or `expert` axis): a stage would have to hand the expert branch on beside the stream"
            )
        if (self.q_latent_scale != 1.0 or self.kv_latent_scale != 1.0) and not self.latent:
            raise ValueError("q_latent_scale and kv_latent_scale belong to latent attention (kv_lora_rank)")
        if (self.moe_router == "mlp") != (self.router_hidden_size is not None) or (
            self.moe_router == "mlp" and self.expert_axis_name is not None
        ):
            raise ValueError(
                "moe_router mlp routes over router_hidden_size values a token (which belongs to it), outside "
                "pipeline stages: a stage would have to hand the router's state on"
            )
        if (self.moe_router != "softmax" or self.moe_shared_experts) and not self.moe_top_k:
            raise ValueError("moe_router and moe_shared_experts belong to moe_top_k > 0")
        if self.moe_expert_act not in ("swiglu", "relu2") or (
            (self.moe_expert_act != "swiglu" or self.moe_latent_size is not None) and not self.moe_top_k
        ) or (self.moe_latent_size is not None and int(self.moe_latent_size) < 1) or (
            self.moe_shared_intermediate_size is not None and (not self.moe_shared_experts or int(self.moe_shared_intermediate_size) < 1)
        ):
            raise ValueError(
                "moe_expert_act is swiglu or relu2; it and moe_latent_size >= 1 belong to moe_top_k > 0, and "
                f"moe_shared_intermediate_size >= 1 to moe_shared_experts (got {self.moe_expert_act!r}, "
                f"{self.moe_latent_size}, {self.moe_shared_intermediate_size})"
            )
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
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
            if any(v is None or int(v) < 1 for v in sizes) or self.qk_rope_head_dim % 2 or (
                self.q_lora_rank is not None and int(self.q_lora_rank) < 1
            ) or (self.q_lora_rank is None and (self.q_latent_scale != 1.0 or self.indexer_types is not None)):
                raise ValueError(
                    "latent attention needs kv_lora_rank, qk_nope_head_dim, an even qk_rope_head_dim and v_head_dim, and "
                    "q_lora_rank >= 1 or None (queries straight from the stream: no query latent to scale, none for an "
                    f"indexer to read) (got {(self.q_lora_rank,) + sizes})"
                )
            if self.quantized_matmul != "none" or self.seq_axis_name is not None:
                raise ValueError("latent attention runs without quantized_matmul and outside a `seq` axis")
        if self.indexer_types is not None:
            setattr_("indexer_types", tuple(self.indexer_types))
            refused = [
                what for what, there in (
                    ("without latent attention (kv_lora_rank): the indexer reads the normed query latent", not self.latent),
                    ("under parallel_block", self.parallel_block), ("under shortcut_block", self.shortcut_block),
                    ("under mixer_block", self.mixer_block), ("under a `seq` axis", self.seq_axis_name is not None),
                    ("inside pipeline stages (an `expert` axis: a stage hands on the stream alone, not the picks)", self.expert_axis_name is not None),
                    ("with moe_router mlp (one value is handed from layer to layer, and the router's state is it)", self.moe_router == "mlp"),
                ) if there
            ]
            if refused:
                raise ValueError("an indexer (indexer_types) does not run " + "; ".join(refused))
            sizes = (self.index_n_heads, self.index_head_dim, self.index_topk)
            if (
                len(self.indexer_types) != self.n_layers or set(self.indexer_types) - {INDEX_FULL, INDEX_SHARED}
                or self.indexer_types[0] != INDEX_FULL or min(sizes) < 1 or not self.qk_rope_head_dim <= self.index_head_dim
            ):
                raise ValueError(
                    f"indexer_types needs `{INDEX_FULL}` or `{INDEX_SHARED}` for each of the {self.n_layers} layers, the first "
                    f"`{INDEX_FULL}` (a shared layer reads the picks of a layer before it), and index_n_heads, index_topk >= 1 with "
                    f"index_head_dim >= qk_rope_head_dim, whose first part rotary turns (got {self.indexer_types}, {sizes})"
                )
        elif self.index_n_heads or self.index_head_dim or self.index_topk:
            raise ValueError("index_n_heads, index_head_dim and index_topk belong to indexer_types")

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
        ``moe_every``-th block; under ``mixer_block`` the layers of type ``experts``."""
        if self.mixer_block:
            return self.layer_type(i) == EXPERTS
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
        values in a store of its own (``models/cache_kinds.py``)."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) == SLIDING)

    @property
    def retention_layers(self) -> Tuple[int, ...]:
        """The power-retention layers, in order: serving keeps a state a decode
        lane for each (``models/cache_kinds.py``), and no token's keys or values."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) == RETENTION)

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        """The layers with a Mamba-2 mixer, in order: serving keeps a state and
        a convolution tail a decode lane for each (``models/cache_kinds.py``),
        beside what their attention heads keep (a ``mamba2`` layer has none)."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) in (HYBRID, MAMBA2))

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        """The layers with a Gated-DeltaNet mixer, in order: serving keeps a
        delta-rule state and a convolution tail a decode lane for each
        (``models/cache_kinds.py``), and no token's keys or values."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) == LINEAR)

    @property
    def rowless_layers(self) -> Tuple[int, ...]:
        """The layers that keep no row a token in any pool or ring: power
        retention's, Gated DeltaNet's, and under ``mixer_block`` a Mamba-2 mixer or experts alone."""
        return tuple(i for i in range(self.n_layers) if self.layer_type(i) in (RETENTION, MAMBA2, EXPERTS, LINEAR))

    @property
    def linear_key_width(self) -> int:
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def linear_value_width(self) -> int:
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def linear_channel_decay(self) -> bool:
        """Whether a linear layer's decay is a value a channel of the key (Kimi Delta Attention's form of the mixer)."""
        return self.linear_decay_floor is not None

    @property
    def linear_channels(self) -> int:
        """What a Gated-DeltaNet mixer's convolution runs over: q, k and v."""
        return 2 * self.linear_key_width + self.linear_value_width

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_channels(self) -> int:
        """What the convolution runs over: x, B and C."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    @property
    def attn_sublayers(self) -> int:
        """Attention sublayers a block: each keeps its own row a token in the serving cache."""
        return 2 if self.shortcut_block else 1

    @property
    def paged_layers(self) -> int:
        """How many rows of the paged pool a token owns: one an attention sublayer of each layer that keeps its rows there."""
        return (self.n_layers - len(self.window_layers) - len(self.rowless_layers)) * self.attn_sublayers

    @property
    def index_layers(self) -> Tuple[int, ...]:
        """The layers that hold an indexer, in order: serving keeps their index
        keys a token in an array of its own (``models/cache_kinds.py``)."""
        return tuple(i for i, kind in enumerate(self.indexer_types or ()) if kind == INDEX_FULL)

    def index_layer(self, i: int) -> Optional[int]:
        """Layer ``i``'s own place among ``index_layers``; None: it holds no indexer."""
        return self.index_layers.index(i) if i in self.index_layers else None

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


def _rope_first(x: jax.Array, positions: jax.Array, rope: Optional[Rope], factor: float) -> jax.Array:
    """Rotary embeddings on the first ``factor`` of each head of ``x`` [b, h, s,
    d] (the frequencies those of a head that wide), the rest as it is."""
    if factor == 1.0:
        return _rope(x, positions, rope)
    turned = int(x.shape[-1] * factor)
    return jnp.concatenate([_rope(x[..., :turned], positions, rope), x[..., turned:]], axis=-1)


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


def _gated(att: jax.Array, gate: jax.Array) -> jax.Array:
    """What a head attended to, times ``sigmoid`` of its gate (taken in float32), in ``att``'s dtype."""
    return att * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(att.dtype)


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
            # under ``attn_output_gate`` a head of ``wq`` is [query | gate]
            q = dense((cfg.n_heads, hd * (2 if cfg.attn_output_gate else 1)), ("embed", "heads", "head_dim"), "wq")(x)
            k = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wk")(x)
            v = dense((cfg.kv_heads, hd), ("embed", "kv", "head_dim"), "wv")(x)
            k = _times(k, cfg.key_multiplier)
            if cfg.attn_output_gate:
                q, gate = q[..., :hd], q[..., hd:]
            if cfg.qk_norm:
                ones = _maybe_partition(cfg.partition_params, nn.initializers.ones, ("head_dim",))
                q = _rms(q, self.param("q_norm", ones, (hd,), cfg.param_dtype), cfg.norm_eps)
                k = _rms(k, self.param("k_norm", ones, (hd,), cfg.param_dtype), cfg.norm_eps)
            # [b, s, h, d] -> [b, h, s, d]
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

            positions = jnp.arange(s)
            if cfg.seq_axis_name is not None:
                # manual SPMD inside a pipeline stage: s is the LOCAL shard
                # length; rope positions are global (contiguous assignment)
                positions = positions + jax.lax.axis_index(cfg.seq_axis_name) * s
            rope = cfg.rope(self.layer_type)
            q = _rope_first(q, positions, rope, cfg.partial_rotary_factor)
            k = _rope_first(k, positions, rope, cfg.partial_rotary_factor)
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
        if cfg.attn_output_gate:
            with jax.named_scope("attn.gate"):
                out = _gated(out, gate.transpose(0, 2, 1, 3))
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


def _cca_param_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Any, ...], Any]]:
    """Compressed attention's leaves beside its five projections: name ->
    (shape, logical axes, initialiser).  The convolutions as a ``Conv1d`` is
    drawn (uniform within fan-in ** -0.5: a channel's ``cca_time0`` taps, a
    head's ``cca_time1 x head_dim``), the keys' temperature ``tau`` zero."""
    hq, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    b0, b1 = cfg.cca_time0 ** -0.5, (cfg.cca_time1 * hd) ** -0.5
    return {
        "conv0_w": ((cfg.cca_time0, hq + hk, hd), (None, None, "head_dim"), _uniform(-b0, b0)),
        "conv0_b": ((hq + hk, hd), (None, "head_dim"), _uniform(-b0, b0)),
        "conv1_w": ((cfg.cca_time1, hq + hk, hd, hd), (None, None, None, "head_dim"), _uniform(-b1, b1)),
        "conv1_b": ((hq + hk, hd), (None, "head_dim"), _uniform(-b1, b1)),
        "tau": ((hk,), (None,), nn.initializers.zeros),
    }


def _causal_taps(x: jax.Array, taps: int) -> Tuple[jax.Array, ...]:
    """``x`` [b, s, ...] as a causal convolution's taps see it: tap ``i`` of
    token ``t`` is ``x[t - (taps - 1) + i]``, zeros before the sequence's start."""
    rows = jnp.pad(x, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return tuple(rows[:, i: i + x.shape[1]] for i in range(taps))


def _cca_mix(cfg: TransformerConfig, p: Dict[str, Any], q: jax.Array, k: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The latent's mixing over time and heads, on the projections ``q`` [b, s,
    heads, hd] and ``k`` [b, s, kv, hd]: the heads side by side pass a depthwise
    causal convolution and then one that mixes each head's channels; to the
    result is added the q-k mean (a query head with its KV head, halved; for a
    key, the mean of that over its group's query heads)."""
    b, s, hq, hd = q.shape
    hk, dt = k.shape[2], q.dtype
    mean_q = (q + jnp.repeat(k, hq // hk, axis=2)) * 0.5
    mean_k = jnp.mean(mean_q.reshape(b, s, hk, hq // hk, hd), axis=3)
    z = jnp.concatenate([q, k], axis=2)
    w0, w1 = p["conv0_w"].astype(dt), p["conv1_w"].astype(dt)
    z = sum(w0[i] * rows for i, rows in enumerate(_causal_taps(z, cfg.cca_time0))) + p["conv0_b"].astype(dt)
    z = sum(jnp.einsum("bshc,hcd->bshd", rows, w1[i]) for i, rows in enumerate(_causal_taps(z, cfg.cca_time1)))
    z = z + p["conv1_b"].astype(dt)
    return z[:, :, :hq] + mean_q, z[:, :, hq:] + mean_k


def _l2_heads(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Each head of ``x`` [..., heads, hd] at Euclidean length ``scale`` (a
    scalar, or one a head): the sum of squares in float32, the result in x's dtype."""
    x32 = x.astype(jnp.float32)
    unit = x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True))
    return (unit * jnp.asarray(scale, jnp.float32)[..., None]).astype(x.dtype)


class CompressedAttention(nn.Module):
    """Compressed convolutional attention (CCA; Zyphra, arXiv:2510.04476) over
    the whole sequence: q and k are projected into a latent of ``n_heads`` and
    ``kv_heads`` heads, mixed there over time (``_cca_mix``), brought to length
    ``sqrt(head_dim)`` a head (a key's times ``exp(tau)``, learned a KV head)
    and turned by rotary on the first ``partial_rotary_factor`` of each head;
    the first half of the KV heads take their value from the token, the rest
    from the token before (the value shift); full causal attention runs inside
    the latent and ``wo`` leads out of it.  Training only: a served layer's
    cache would keep, beside K and V a token, the convolutions' tail and the
    newest normed input a lane (``models/serving.py`` refuses it)."""

    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        from determined_tpu.train._quant import make_dot_general

        cfg = self.cfg
        hq, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        p = {
            name: self.param(name, _maybe_partition(cfg.partition_params, init, logical), shape, cfg.param_dtype)
            for name, (shape, logical, init) in _cca_param_shapes(cfg).items()
        }
        dense = lambda feats, logical, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            feats, axis=axis, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            dot_general=make_dot_general(cfg.quantized_matmul),
            kernel_init=_maybe_partition(cfg.partition_params, nn.initializers.lecun_normal(), logical), name=name,
        )
        with jax.named_scope("attn.qkv"):
            q = dense((hq, hd), ("embed", "heads", "head_dim"), "wq")(u)
            k = dense((hk, hd), ("embed", "kv", "head_dim"), "wk")(u)
        with jax.named_scope("attn.cca.mix"):
            q, k = _cca_mix(cfg, p, q, k)
            before = _causal_taps(u, 2)[0]  # the value shift: token t reads token t - 1's normed input
        with jax.named_scope("attn.qkv"):
            v = jnp.concatenate([
                dense((hk - hk // 2, hd), ("embed", "kv", "head_dim"), "wv1")(u),
                dense((hk // 2, hd), ("embed", "kv", "head_dim"), "wv2")(before),
            ], axis=2)
        with jax.named_scope("attn.cca.norm"):
            q = _l2_heads(q, math.sqrt(hd))
            k = _l2_heads(k, math.sqrt(hd) * jnp.exp(p["tau"].astype(jnp.float32)))
        with jax.named_scope("attn.qkv"):
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [b, h, s, d]
            positions, rope = jnp.arange(u.shape[1]), cfg.rope(CCA)
            q, k = (_rope_first(t, positions, rope, cfg.partial_rotary_factor) for t in (q, k))
        with jax.named_scope("attn.full"):
            out = dot_product_attention(q, k, v, causal=True, impl=cfg.attention_impl, mesh=self.mesh)
        with jax.named_scope("attn.out"):
            return dense(cfg.d_model, ("heads", "head_dim", "embed"), "wo", (-2, -1))(out.transpose(0, 2, 1, 3))


class ResidualScale(nn.Module):
    """``(x + res_bias) * res_scale + (f + out_bias) * out_scale``: a block's
    merge of the stream ``x`` with a sublayer's output ``f`` under
    ``residual_scaling``, four learned vectors (scales one, biases zero at the start)."""

    partition: bool = True
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, f: jax.Array) -> jax.Array:
        def vec(name: str, init: Any) -> jax.Array:
            return self.param(name, _maybe_partition(self.partition, init, ("embed",)), (x.shape[-1],), self.param_dtype).astype(x.dtype)

        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        with jax.named_scope("block.rescale"):
            return (x + vec("res_bias", zeros)) * vec("res_scale", ones) + (f + vec("out_bias", zeros)) * vec("out_scale", ones)


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
    """Latent attention's leaves: name -> (shape, logical axes, initialiser):
    the queries' bottleneck (or ONE matrix ``wq`` without one), under
    ``attn_output_gate`` the head-wise gate's ``w_gate``, and the rest."""
    d, h = cfg.d_model, cfg.n_heads
    qk, rope = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
    kernel, ones = nn.initializers.lecun_normal(), nn.initializers.ones
    queries = {"wq": ((d, h, qk), ("embed", "heads", "head_dim"), kernel)} if cfg.q_lora_rank is None else {
        "wq_a": ((d, cfg.q_lora_rank), ("embed", None), kernel),
        "q_norm": ((cfg.q_lora_rank,), (None,), ones),
        "wq_b": ((cfg.q_lora_rank, h, qk), (None, "heads", "head_dim"), kernel),
    }
    return {
        **queries,
        **({"w_gate": ((d, h), ("embed", "heads"), kernel)} if cfg.attn_output_gate else {}),
        "wkv_a": ((d, cfg.kv_lora_rank + rope), ("embed", None), kernel),
        "kv_norm": ((cfg.kv_lora_rank,), (None,), ones),
        "wkv_b": ((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim), (None, "heads", "head_dim"), kernel),
        "wo": ((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed"), nn.initializers.lecun_normal(in_axis=(0, 1))),
    }


def _index_param_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Any, ...], Any]]:
    """An indexer's leaves, beside latent attention's in the subtree of a layer
    that holds one: queries from the normed query latent, ONE key a token and a
    weight a head from the layer's normed input, the key's LayerNorm (weight and bias)."""
    d, h, k = cfg.d_model, cfg.index_n_heads, cfg.index_head_dim
    kernel = nn.initializers.lecun_normal()
    return {
        "index_wq_b": ((cfg.q_lora_rank, h, k), (None, None, None), kernel),
        "index_wk": ((d, k), ("embed", None), kernel),
        "index_k_norm": ((k,), (None,), nn.initializers.ones),
        "index_k_bias": ((k,), (None,), nn.initializers.zeros),
        "index_w": ((d, h), ("embed", None), kernel),
    }


class LatentAttention(nn.Module):
    """Multi-head latent attention over the whole sequence (training, and
    what ``init`` builds for serving): the projections of ``_latent_project``
    and the expanded, causal form of ``_latent_attend_local``; the serving
    forward below reads the same leaves.  Under ``indexer_types`` a query
    attends over the keys ``picked`` marks (a mask [b, s, s]; ``indexer``
    "full": the layer's own picks, which it also hands on; "shared": those it
    was handed), once the sequence is longer than ``index_topk``; up to there
    nothing is left out and nothing is scored.  Returns (what attention adds,
    the mask)."""

    cfg: TransformerConfig
    indexer: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, picked: Any = None) -> Tuple[jax.Array, Any]:
        cfg = self.cfg
        shapes = {**_latent_param_shapes(cfg), **(_index_param_shapes(cfg) if self.indexer == INDEX_FULL else {})}
        p = {
            name: self.param(name, _maybe_partition(cfg.partition_params, init, logical), shape, cfg.param_dtype)
            for name, (shape, logical, init) in shapes.items()
        }
        s = x.shape[1]
        with jax.named_scope("attn.qkv"):
            q_nope, q_rope, c_kv, k_r, c_q = _latent_project(cfg, p, x, jnp.arange(s), cfg.rope(FULL))
        if self.indexer == INDEX_FULL:
            picked = None
            if s > cfg.index_topk:
                with jax.named_scope("dsa"):
                    with jax.named_scope("dsa.project"):
                        q_i, w, k_i = _index_project(cfg, p, c_q, x, jnp.arange(s), cfg.rope(FULL))
                    with jax.named_scope("dsa.index"):
                        scores = index_scores(q_i, w, k_i)
                    with jax.named_scope("dsa.topk"):
                        picked = index_topk_mask(scores, jnp.tril(jnp.ones((s, s), bool)), cfg.index_topk)
        with jax.named_scope("attn.full"):
            out = _latent_attend_local(cfg)(q_nope, q_rope, c_kv, k_r, p["wkv_b"], None, 0, picked)
        if cfg.attn_output_gate:
            with jax.named_scope("attn.gate"):
                out = _head_gated(cfg, p, x, out)
        with jax.named_scope("attn.out"):
            return jnp.einsum("bshv,hvD->bsD", out, p["wo"].astype(cfg.dtype)), picked


def _times(x: jax.Array, scalar: float) -> jax.Array:
    """``x`` times one of muP's scalars; at 1 the program has no such product."""
    return x if scalar == 1.0 else x * scalar


def _uniform(low: float, high: float, of=lambda v: v):
    """An initialiser: ``of`` of a uniform draw, taken in float32."""
    return lambda key, shape, dtype=jnp.float32: of(jax.random.uniform(key, shape, jnp.float32, low, high)).astype(dtype)


#: a head's step bias and decay rate as Mamba-2 draws them: a step log-uniform in (0.001, 0.1) through the softplus's
#: inverse, ``A`` in (1, 16)
_STEP_BIAS_INIT = _uniform(math.log(1e-3), math.log(0.1), lambda u: jnp.exp(u) + jnp.log(-jnp.expm1(-jnp.exp(u))))
_DECAY_LOG_INIT = _uniform(1.0, 16.0, jnp.log)


def _ssm_param_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Any, ...], Any]]:
    """The Mamba-2 mixer's leaves: name -> (shape, logical axes, initialiser).
    ``A_log`` and ``dt_bias`` as Mamba-2 draws them (``A`` in (1, 16), a step
    log-uniform in (0.001, 0.1) through the softplus's inverse: a fresh head
    remembers between ~1 and ~1,000 tokens), the convolution as a depthwise
    ``Conv1d`` is drawn (uniform within ``ssm_conv ** -0.5``), ``D`` ones."""
    d, width, ch, h = cfg.d_model, cfg.ssm_width, cfg.ssm_channels, cfg.ssm_heads
    kernel, ones, bound = nn.initializers.lecun_normal(), nn.initializers.ones, cfg.ssm_conv ** -0.5
    return {
        "w_in": ((d, 2 * width + 2 * cfg.ssm_groups * cfg.ssm_state + h), ("embed", "mlp"), kernel),
        "conv_w": ((cfg.ssm_conv, ch), (None, "mlp"), _uniform(-bound, bound)),
        "conv_b": ((ch,), ("mlp",), _uniform(-bound, bound)),
        "dt_bias": ((h,), (None,), _STEP_BIAS_INIT),
        "A_log": ((h,), (None,), _DECAY_LOG_INIT),
        "D": ((h,), (None,), ones),
        "norm": ((width,), ("mlp",), ones),
        "w_out": ((width, d), ("mlp", "embed"), kernel),
    }


def _ssm_project(cfg: TransformerConfig, p: Dict[str, Any], u: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The in-projection of the normed input ``u`` [..., d] with its
    multipliers: the gate ``z`` [..., width], what the convolution takes (x, B,
    C side by side) [..., channels], and the step before its bias [..., heads]."""
    width, state, h = cfg.ssm_width, cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads
    out = _times(u, cfg.ssm_in_multiplier) @ p["w_in"].astype(cfg.dtype)
    if set(cfg.ssm_multipliers) != {1.0}:
        spans = np.repeat(np.asarray(cfg.ssm_multipliers, np.float32), [width, width, state, state, h])
        out = out * jnp.asarray(spans, cfg.dtype)
    return out[..., :width], out[..., width: width + cfg.ssm_channels], out[..., width + cfg.ssm_channels:]


def _ssm_conv(cfg: TransformerConfig, p: Dict[str, Any], rows: jax.Array) -> jax.Array:
    """The causal depthwise convolution and its SiLU: ``rows`` [b, s + ssm_conv
    - 1, channels] is what came before (zeros before a sequence's start) and then
    the ``s`` tokens; token ``t`` is ``silu(sum_i w_i rows[t + i] + bias)``."""
    return _causal_conv(p["conv_w"], p["conv_b"], rows, cfg.dtype)


def _causal_conv(w: jax.Array, bias: Optional[jax.Array], rows: jax.Array, dtype: Any) -> jax.Array:
    """A causal depthwise convolution of ``w`` [taps, channels] (and its bias, or
    None), taken in ``dtype``, over ``rows`` [b, s + taps - 1, channels], and its
    SiLU: [b, s, channels]."""
    taps = w.shape[0]
    s = rows.shape[1] - taps + 1
    w = w.astype(dtype)
    out = sum(w[i] * rows[:, i: i + s] for i in range(taps))
    return nn.silu(out if bias is None else out + bias.astype(dtype))


def _ssm_split(cfg: TransformerConfig, xbc: jax.Array, dt: jax.Array, p: Dict[str, Any]):
    """The convolution's output [..., channels] as x [..., heads, P], B and C
    [..., groups, N]; the step after its bias and softplus, float32 [...,
    heads]; ``A`` (negative) and ``D``, float32 [heads]."""
    width, state = cfg.ssm_width, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :width].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = xbc[..., width: width + state].reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    c = xbc[..., width + state:].reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    step = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return x, b, c, step, -jnp.exp(p["A_log"].astype(jnp.float32)), p["D"].astype(jnp.float32)


def _ssm_out(cfg: TransformerConfig, p: Dict[str, Any], y: jax.Array, z: jax.Array) -> jax.Array:
    """``y`` [..., heads, P] float32 gated by ``silu(z)``, then RMSNorm over
    each group's channels (the gate before the norm), the out-projection and its
    multiplier."""
    lead = z.shape[:-1]
    gated = y.reshape(*lead, cfg.ssm_groups, -1) * nn.silu(z.astype(jnp.float32)).reshape(*lead, cfg.ssm_groups, -1)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps)
    normed = gated.reshape(*lead, -1).astype(cfg.dtype) * p["norm"].astype(cfg.dtype)
    return _times(normed @ p["w_out"].astype(cfg.dtype), cfg.ssm_out_multiplier)


class Mamba2(nn.Module):
    """A Mamba-2 mixer over the whole sequence, in its chunked form
    (``ops/ssm.py ssm_scan``): what ``init`` builds for serving, and the wide
    oracle of the serving forward (``models/cache_kinds.py``), which reads the
    same leaves through the same functions and carries a state and the
    convolution's tail between its calls."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        cfg = self.cfg
        p = {
            name: self.param(name, _maybe_partition(cfg.partition_params, init, logical), shape, cfg.param_dtype)
            for name, (shape, logical, init) in _ssm_param_shapes(cfg).items()
        }
        with jax.named_scope("attn.ssm"):
            z, xbc, dt = _ssm_project(cfg, p, u)
            xbc = _ssm_conv(cfg, p, jnp.pad(xbc, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0))))
            x, b, c, step, a, skip = _ssm_split(cfg, xbc, dt, p)
            return _ssm_out(cfg, p, ssm_scan(x, b, c, step, a, skip, cfg.ssm_chunk), z)


def _gdn_param_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Any, ...], Any]]:
    """The Gated-DeltaNet mixer's leaves: name -> (shape, logical axes,
    initialiser).  ``w_in``'s columns are ``[q | k | v | z]`` and ``w_ba``'s ``[b |
    a]``, each segment head after head (the published matrices order theirs key
    head by key head: a fixed permutation); ``A_log`` and ``dt_bias`` as the
    Mamba-2 mixer's are drawn (``_ssm_param_shapes``: a fresh head remembers
    between ~1 and ~1,000 tokens), the convolution as a depthwise ``Conv1d`` is
    (no bias), the gated norm's weight ONE head's width, shared by the heads."""
    d, hv = cfg.d_model, cfg.linear_value_heads
    kernel, bound = nn.initializers.lecun_normal(), cfg.linear_conv ** -0.5
    # a decay a channel: ``w_ba`` holds ``b`` alone, and the decay's own projection and bias are a key's width a head
    channel = cfg.linear_channel_decay
    decay = hv * cfg.linear_key_head_dim if channel else hv
    return {
        "w_in": ((d, cfg.linear_channels + cfg.linear_value_width), ("embed", "mlp"), kernel),
        "w_ba": ((d, hv if channel else 2 * hv), ("embed", None), kernel),
        **({"w_decay": ((d, decay), ("embed", "mlp"), kernel)} if channel else {}),
        "conv_w": ((cfg.linear_conv, cfg.linear_channels), (None, "mlp"), _uniform(-bound, bound)),
        "dt_bias": ((decay,), (None,), _STEP_BIAS_INIT), "A_log": ((hv,), (None,), _DECAY_LOG_INIT),
        "norm": ((cfg.linear_value_head_dim,), (None,), nn.initializers.ones),
        "w_out": ((cfg.linear_value_width, d), ("mlp", "embed"), kernel),
    }


def _gdn_project(cfg: TransformerConfig, p: Dict[str, Any], u: jax.Array):
    """The two in-projections of the normed input ``u`` [..., d]: what the
    convolution takes (q, k, v side by side) [..., channels], the output gate
    ``z`` [..., value width], and ``b`` and ``a`` [..., value heads] (the write
    strength and the decay before their sigmoid and softplus); a decay a channel
    is a THIRD projection, ``a`` [..., value heads x K]."""
    out, ba = u @ p["w_in"].astype(cfg.dtype), u @ p["w_ba"].astype(cfg.dtype)
    ch, hv = cfg.linear_channels, cfg.linear_value_heads
    if cfg.linear_channel_decay:
        return out[..., :ch], out[..., ch:], ba, u @ p["w_decay"].astype(cfg.dtype)
    return out[..., :ch], out[..., ch:], ba[..., :hv], ba[..., hv:]


def _gdn_conv(p: Dict[str, Any], rows: jax.Array) -> jax.Array:
    """The mixer's causal depthwise convolution (no bias) and its SiLU over ``rows``
    [b, s + linear_conv - 1, channels], in float32 whatever the rows' dtype: what
    it feeds (the l2 norms, the rule) is float32, so the taps' products and sums
    are not rounded to the compute dtype on their way there."""
    return _causal_conv(p["conv_w"], None, rows.astype(jnp.float32), jnp.float32)


def _gdn_split(cfg: TransformerConfig, qkv: jax.Array, b: jax.Array, a: jax.Array, p: Dict[str, Any]):
    """The convolution's output [..., channels] as each VALUE head's q and k
    [..., value heads, K] (float32, at unit length, q times ``K ** -0.5``; a key
    head serves its consecutive value heads) and v [..., value heads, V]; the
    decay's logarithm ``g = -exp(A_log) softplus(a + dt_bias)`` and the write
    strength ``beta = sigmoid(b)``, float32 [..., value heads]; a decay a channel
    ``g = linear_decay_floor * sigmoid(exp(A_log) (a + dt_bias))`` [..., value
    heads, K], ``A_log`` a head's."""
    f32, kw, lead = jnp.float32, cfg.linear_key_width, qkv.shape[:-1]
    hk, hv, dk = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim
    q = l2_heads(qkv[..., :kw].reshape(*lead, hk, dk)) * dk ** -0.5
    k = l2_heads(qkv[..., kw: 2 * kw].reshape(*lead, hk, dk))
    v = qkv[..., 2 * kw:].reshape(*lead, hv, cfg.linear_value_head_dim)
    if cfg.linear_channel_decay:
        a = (a.astype(f32) + p["dt_bias"].astype(f32)).reshape(*lead, hv, dk)
        g = cfg.linear_decay_floor * jax.nn.sigmoid(jnp.exp(p["A_log"].astype(f32))[:, None] * a)
    else:
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a.astype(f32) + p["dt_bias"].astype(f32))
    return jnp.repeat(q, hv // hk, axis=-2), jnp.repeat(k, hv // hk, axis=-2), v, g, jax.nn.sigmoid(b.astype(f32))


def _gdn_out(cfg: TransformerConfig, p: Dict[str, Any], o: jax.Array, z: jax.Array) -> jax.Array:
    """``o`` [..., value heads, V] float32 under RMSNorm over each head's
    values, times the norm's weight, THEN times ``silu(z)`` (the norm before the
    gate, all in float32; under a decay a channel the gate is ``sigmoid(z)``), and
    the out-projection."""
    f32, lead = jnp.float32, z.shape[:-1]
    normed = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps) * p["norm"].astype(f32)
    gated = normed * (jax.nn.sigmoid if cfg.linear_channel_decay else nn.silu)(z.astype(f32)).reshape(*lead, cfg.linear_value_heads, -1)
    return gated.reshape(*lead, -1).astype(cfg.dtype) @ p["w_out"].astype(cfg.dtype)


class GatedDeltaNet(nn.Module):
    """A Gated-DeltaNet mixer (Yang et al., arXiv:2412.06464, as Qwen3-Next runs
    it; under ``linear_decay_floor`` Kimi Delta Attention's, arXiv:2510.26692,
    as Ling-3.0 runs it) over the whole sequence, in its chunked form (``ops/gated_delta.py
    gdn_chunk``): what ``init`` builds for serving, and the wide oracle of the
    serving forward (``models/cache_kinds.py``), which reads the same leaves
    through the same functions and carries a state and the convolution's tail
    between its calls."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        cfg = self.cfg
        p = {
            name: self.param(name, _maybe_partition(cfg.partition_params, init, logical), shape, cfg.param_dtype)
            for name, (shape, logical, init) in _gdn_param_shapes(cfg).items()
        }
        with jax.named_scope("attn.gdn"):
            qkv, z, b, a = _gdn_project(cfg, p, u)
            qkv = _gdn_conv(p, jnp.pad(qkv, ((0, 0), (cfg.linear_conv - 1, 0), (0, 0))))
            q, k, v, g, beta = _gdn_split(cfg, qkv, b, a, p)
            empty = jnp.zeros((u.shape[0], cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim), jnp.float32)
            o, _ = gdn_chunk(q, k, v, g, beta, empty, jnp.ones(u.shape[:2], bool), chunk=cfg.linear_chunk)
            return _gdn_out(cfg, p, o, z)


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
            h = nn.silu(_times(gate, cfg.mlp_multipliers[0])) * up
            if cfg.partition_params:
                h = with_sharding_constraint(h, ("batch", "length", "mlp"), mesh=self.mesh)
            return _times(dense(cfg.d_model, ("mlp", "embed"), "w_down")(h), cfg.mlp_multipliers[1])


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Any = None
    use_moe: bool = False
    layer_type: str = FULL
    indexer: Optional[str] = None  # what ``indexer_types`` says of this layer

    @nn.compact
    def __call__(self, x: jax.Array, state: Any = None) -> Tuple[jax.Array, jax.Array, Any]:
        """``x`` [b, s, d] -> (``x``, the auxiliary loss, what the block hands
        the next beside the stream: under the "mlp" router its router's state
        [b, s, router_hidden_size], from the last expert block's ``state``;
        under ``indexer_types`` the picks of the newest layer that holds an
        indexer, as a mask; else None)."""
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.norm_eps, partition=cfg.partition_params, param_dtype=cfg.param_dtype, kind=cfg.norm, name=name
        )

        def merge(name: str, x: jax.Array, f: jax.Array) -> jax.Array:
            if not cfg.residual_scaling:
                return x + f
            return ResidualScale(cfg.partition_params, cfg.param_dtype, name=name)(x, f)

        def ffn(h: jax.Array) -> Tuple[jax.Array, jax.Array, Any]:
            """The block's MLP or experts on the normed input, the auxiliary
            loss, and what the block hands on (the "mlp" router's state; else
            what it was handed)."""
            if self.use_moe and cfg.moe_top_k:
                from determined_tpu.models.moe import RoutedExperts

                mlp = cfg.moe_router == "mlp"
                out = RoutedExperts(
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
                    shared_gate=cfg.moe_shared_gate,
                    zero_experts=cfg.moe_zero_experts,
                    expert_act=cfg.moe_expert_act,
                    latent_size=cfg.moe_latent_size,
                    shared_d_ff=cfg.moe_shared_intermediate_size,
                    param_dtype=cfg.param_dtype,
                    router_hidden=cfg.router_hidden_size or 0,
                    norm_eps=cfg.norm_eps,
                    name="moe",
                )(h, *((state,) if mlp else ()))
                return out if mlp else (*out, state)
            if self.use_moe:
                from determined_tpu.models.moe import MoE

                return *MoE(
                    num_experts=cfg.moe_experts,
                    d_ff=cfg.ff_dim,
                    capacity_factor=cfg.moe_capacity_factor,
                    dtype=cfg.dtype,
                    partition=cfg.partition_params,
                    expert_axis_name=cfg.expert_axis_name,
                    name="moe",
                )(h), state
            return MLP(cfg, self.mesh, name="mlp")(h), jnp.zeros((), jnp.float32), state

        def attend(name: str, h: jax.Array) -> jax.Array:
            """What the attention sublayer ``name`` adds, from the normed input."""
            nonlocal state
            if self.layer_type == LINEAR:
                return GatedDeltaNet(cfg, name="gdn")(h)
            if cfg.latent:
                out, picked = LatentAttention(cfg, self.indexer, name=name)(h, state if self.indexer else None)
                state = picked if self.indexer else state
                return out
            if self.layer_type == RETENTION:
                return Retention(cfg, name=name)(h)
            if self.layer_type == CCA:
                return CompressedAttention(cfg, self.mesh, name=name)(h)
            if self.layer_type == HYBRID:
                # attention heads and Mamba-2 heads read the one norm side by side
                att = Attention(cfg, self.mesh, self.layer_type, name=name)(_times(h, cfg.attention_in_multiplier))
                return _times(att, cfg.attention_out_multiplier) + Mamba2(cfg, name="ssm")(h)
            return Attention(cfg, self.mesh, self.layer_type, name=name)(h)

        h = norm("ln1")(x)
        if cfg.mixer_block:
            # one norm, ONE mixer, one residual: the layer's type says which
            aux, handed = jnp.zeros((), jnp.float32), state
            if self.layer_type == EXPERTS:
                y, aux, handed = ffn(h)
            else:
                y = Mamba2(cfg, name="ssm")(h) if self.layer_type == MAMBA2 else attend("attn", h)
            x = x + y
            if cfg.partition_params:
                x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=self.mesh)
            return x, aux, handed
        att = attend("attn", h)
        if cfg.shortcut_block:
            # the experts read the first sublayer's second norm and join the stream after the second MLP
            x = x + att
            u = norm("ln2")(x)
            y, aux, handed = ffn(u)
            x = x + MLP(cfg, self.mesh, name="mlp")(u)
            x = x + attend("attn_1", norm("ln1_1")(x))
            x = x + MLP(cfg, self.mesh, name="mlp_1")(norm("ln2_1")(x)) + y
        elif cfg.parallel_block:
            # one norm: the MLP or the experts read what attention read
            y, aux, handed = ffn(h)
            x = x + att + y
        else:
            x = merge("rescale1", x, att)
            y, aux, handed = ffn(norm("ln2")(x))
            x = merge("rescale2", x, y)
        if cfg.partition_params:
            x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=self.mesh)
        return x, aux, handed


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
            x = _times(embed(tokens), cfg.embedding_multiplier)
            if cfg.partition_params:
                x = with_sharding_constraint(x, ("batch", "length", "embed"), mesh=self.mesh)
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, prevent_cse=False)
        aux_total = jnp.zeros((), jnp.float32)
        state = None  # what a layer hands the next beside the stream (the "mlp" router's state)
        for i in range(cfg.n_layers):
            indexer = cfg.indexer_types[i] if cfg.indexer_types else None
            x, aux, state = block_cls(cfg, self.mesh, cfg.use_moe(i), cfg.layer_type(i), indexer, name=f"block_{i}")(x, state)
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
            out = _times(lm_head(x).astype(jnp.float32), cfg.logit_scale)
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
            return blk.apply({"params": p}, h)[:2]  # no block of a stage hands a state on (LMTrial._cfg)

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


# The serving cache's sizes, functions of the config alone (the forward that
# reads the cache is ``models/serving.py``, over ``models/cache_kinds.py``).


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
    layer caches no token: ``state_bytes_per_slot``; nor does a Gated-DeltaNet
    layer: ``gdn_bytes_per_slot``; nor does a Mamba-2 mixer or an expert layer
    alone, under ``mixer_block``)."""
    values = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) if cfg.latent else 2 * cfg.kv_heads * cfg.head_dim
    return (cfg.n_layers - len(cfg.rowless_layers)) * cfg.attn_sublayers * values * jnp.dtype(cfg.dtype).itemsize


#: the dtype of a state a lane holds (a retention layer's and its normaliser, a Mamba-2 layer's): sums over a whole context
STATE_DTYPE = jnp.float32


def state_pool_shapes(cfg: TransformerConfig, lanes: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The retention layers' state pool and its normaliser (``ops/retention.py
    state_shapes``): a slot a decode lane a layer."""
    return state_shapes(len(cfg.retention_layers), lanes, cfg.kv_heads, cfg.head_dim)


def recent_rows_shapes(cfg: TransformerConfig, lanes: int) -> Tuple[Tuple[int, ...], ...]:
    """The retention layers' recent rows a decode lane (``ops/retention.py
    recent_shapes``): the tokens a decode step has not folded into the state yet."""
    return recent_shapes(len(cfg.retention_layers), lanes, cfg.kv_heads, cfg.head_dim)


def state_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Bytes of ONE retention layer's state and normaliser in one lane: what a
    request holds of such a layer whatever its length (0 without such layers)."""
    if not cfg.retention_layers:
        return 0
    state, norm = state_pool_shapes(cfg, 1)
    return (math.prod(state[1:]) + math.prod(norm[1:])) * jnp.dtype(STATE_DTYPE).itemsize


def ssm_pool_shapes(cfg: TransformerConfig, lanes: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The Mamba-2 layers' state pool (``ops/ssm.py state_shape``: a slot a
    decode lane a layer and one scratch slot) and the convolution's tails: the
    last ``ssm_conv - 1`` rows of its input a lane a layer."""
    layers = len(cfg.ssm_layers)
    return (
        ssm_pool_shape(layers, lanes, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
        (layers, lanes, cfg.ssm_conv - 1, cfg.ssm_channels),
    )


def gdn_pool_shapes(cfg: TransformerConfig, lanes: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The Gated-DeltaNet layers' state pool (``ops/gated_delta.py state_shape``:
    a slot a decode lane a layer and one scratch slot) and the convolution's
    tails: the last ``linear_conv - 1`` rows of its input a lane a layer."""
    layers = len(cfg.linear_layers)
    return (
        gdn_pool_shape(layers, lanes, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim),
        (layers, lanes, cfg.linear_conv - 1, cfg.linear_channels),
    )


def gdn_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Bytes of ONE Gated-DeltaNet layer's state in one lane: what a decode step
    reads and writes of it, whatever the context (the tail's 48 KB are not counted)."""
    return cfg.linear_value_heads * cfg.linear_key_head_dim * cfg.linear_value_head_dim * jnp.dtype(STATE_DTYPE).itemsize


def ssm_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Bytes of ONE Mamba-2 layer's state in one lane: what a decode step reads
    and writes of it, whatever the context (the tail's 30 KB are not counted)."""
    return cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * jnp.dtype(STATE_DTYPE).itemsize


# What ``LatentAttention`` above and the serving forward both run.


def _rms_apply(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with the exact numerics of the ``RMSNorm`` module."""
    with jax.named_scope("serve.norm"):
        return _rms(x, scale, eps)


def _latent_project(cfg, p, h, positions, rope):
    """A latent layer's projections of the normed input ``h`` [b, s, d]; both
    latents times their scale after their norm (``c_kv`` is what serving caches)."""
    dt, r = cfg.dtype, cfg.kv_lora_rank
    if cfg.q_lora_rank is None:  # no query latent: the heads straight from the stream
        c_q, q = None, jnp.einsum("bsd,dhk->bhsk", h, p["wq"].astype(dt))
    else:
        c_q = _times(_rms_apply(h @ p["wq_a"].astype(dt), p["q_norm"], cfg.norm_eps), cfg.q_latent_scale)
        q = jnp.einsum("bsr,rhk->bhsk", c_q, p["wq_b"].astype(dt))
    kv = h @ p["wkv_a"].astype(dt)
    c_kv = _times(_rms_apply(kv[..., :r], p["kv_norm"], cfg.norm_eps), cfg.kv_latent_scale)
    k_r = _rope(kv[:, None, :, r:], positions, rope)[:, 0]
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], _rope(q[..., cfg.qk_nope_head_dim:], positions, rope)
    return q_nope, q_rope, c_kv, k_r, c_q


def _head_gated(cfg, p, h, att):
    """Latent attention's head-wise output gate: what each head attended to, ``att``
    [b, s, heads, v], times ``sigmoid`` (float32) of ONE value a head from the
    normed input ``h``'s own projection ``w_gate``."""
    gate = jax.nn.sigmoid((h @ p["w_gate"].astype(cfg.dtype)).astype(jnp.float32))
    return (att * gate[..., None]).astype(att.dtype)


def _index_project(cfg, p, c_q, h, positions, rope):
    """An indexer's projections, stated once under the whole-sequence form and
    the serving forward: its queries ``[b, s, heads, index_head_dim]`` from the
    normed query latent ``c_q``, its ONE key a token ``[b, s, index_head_dim]``
    from the layer's normed input ``h`` under a LayerNorm with a bias (what
    serving caches), rotary on the first ``qk_rope_head_dim`` of both, and a
    head's weight ``[b, s, heads]`` float32 from ``h``, times ``heads ** -0.5
    * index_head_dim ** -0.5``."""
    dt, r = cfg.dtype, cfg.qk_rope_head_dim
    q = jnp.einsum("bsr,rhk->bhsk", c_q, p["index_wq_b"].astype(dt))
    q = jnp.concatenate([_rope(q[..., :r], positions, rope), q[..., r:]], axis=-1).transpose(0, 2, 1, 3)
    k = _layer_norm(h @ p["index_wk"].astype(dt), p["index_k_norm"], cfg.norm_eps) + p["index_k_bias"].astype(dt)
    k = jnp.concatenate([_rope(k[:, None, :, :r], positions, rope)[:, 0], k[..., r:]], axis=-1)
    w = (h @ p["index_w"].astype(dt)).astype(jnp.float32) * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return q, w, k


def _latent_attend_local(cfg: TransformerConfig):
    """Causal, over this call's own rows, keys and values expanded a head:
    the wide prefill (prompts start at position 0) and the training forward.
    On a TPU at a length worth tiling the flash kernel runs it, q, k and v
    padded with zeros to one width; no ``[heads, s, s]`` array is built.
    ``picked`` [b, s, s] (an indexer's picks a query, as a mask): the flash form
    masks by position alone, so a set a query takes the plain masked softmax."""

    def attend(q_nope, q_rope, c_kv, k_r, wkv_b, cache, i, picked=None):
        dt, nope = cfg.dtype, cfg.qk_nope_head_dim
        kv = jnp.einsum("bsc,chk->bhsk", c_kv, wkv_b.astype(dt))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None], kv.shape[:3] + k_r.shape[-1:])], axis=-1)
        v = kv[..., nope:]
        if picked is not None:
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * cfg.attn_scale
            probs = jax.nn.softmax(jnp.where(picked[:, None], logits, NEG_INF), axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
        elif kernel_form.on_tpu() and q.shape[2] >= 256:
            from determined_tpu.ops.flash_attention import flash_attention

            width = -(-max(q.shape[-1], v.shape[-1]) // 128) * 128
            pad = lambda t: jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))  # noqa: E731
            out = flash_attention(pad(q), pad(k), pad(v), causal=True, scale=cfg.attn_scale)[..., : v.shape[-1]]
        else:
            out = reference_attention(q, k, v, causal=True, scale=cfg.attn_scale)
        return out.transpose(0, 2, 1, 3)

    return attend


#: latent attention's hparams: passed to the config as they are (absent: GQA)
_LATENT_HPARAMS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "softmax_scale")
#: an indexer's: passed as they are too (absent: no layer selects its keys)
_INDEX_HPARAMS = ("indexer_types", "index_n_heads", "index_head_dim", "index_topk")


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
    parallel_block, tie_embeddings with logit_scale, moe_shared_combine;
    layer type cca with cca_time0, cca_time1 and partial_rotary_factor;
    moe_router mlp with router_hidden_size; residual_scaling; shortcut_block
    (two attention sublayers and two dense MLPs a block round the experts),
    moe_router softmax_bias with moe_zero_experts (identity experts),
    q_latent_scale and kv_latent_scale.

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
        carried = [
            what for what, there in (
                ("a cca layer", CCA in (layer_types or ())), ("moe_router mlp", g("moe_router", "softmax") == "mlp"),
                ("residual_scaling", bool(g("residual_scaling", False))),
                ("shortcut_block (its expert branch beside it)", bool(g("shortcut_block", False))),
                ("mixer_block (its layers are not alike: a stage stacks one leaf a layer)", bool(g("mixer_block", False))),
                ("indexer_types (the picks a layer hands the next)", g("indexer_types", None) is not None),
            ) if there
        ]
        if pipe > 1 and carried:
            raise ValueError(
                f"pipe={pipe}: {', '.join(carried)} not run inside pipeline stages: the stage function hands on the "
                "residual stream alone, not the router's state beside it"
            )
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
            router_hidden_size=g("router_hidden_size", None),
            residual_scaling=bool(g("residual_scaling", False)),
            cca_time0=int(g("cca_time0", 2)),
            cca_time1=int(g("cca_time1", 2)),
            partial_rotary_factor=float(g("partial_rotary_factor", 1.0)),
            moe_n_group=int(g("moe_n_group", 1)),
            moe_topk_group=int(g("moe_topk_group", 1)),
            moe_routed_scaling=float(g("moe_routed_scaling", 1.0)),
            moe_shared_experts=int(g("moe_shared_experts", 0)),
            moe_shared_combine=str(g("moe_shared_combine", "sum")),
            moe_zero_experts=int(g("moe_zero_experts", 0)),
            shortcut_block=bool(g("shortcut_block", False)),
            mixer_block=bool(g("mixer_block", False)),
            moe_expert_act=str(g("moe_expert_act", "swiglu")),
            moe_latent_size=g("moe_latent_size", None),
            moe_shared_intermediate_size=g("moe_shared_intermediate_size", None),
            q_latent_scale=float(g("q_latent_scale", 1.0)),
            kv_latent_scale=float(g("kv_latent_scale", 1.0)),
            norm=str(g("norm", "rms")),
            norm_eps=float(g("norm_eps", 1e-6)),
            parallel_block=bool(g("parallel_block", False)),
            tie_embeddings=bool(g("tie_embeddings", False)),
            logit_scale=float(g("logit_scale", 1.0)),
            **{k: g(k, None) for k in _LATENT_HPARAMS},
            **{k: g(k, None if k == "indexer_types" else 0) for k in _INDEX_HPARAMS},
            quantized_matmul=self._quant_mode(),
        )

    #: step metrics the Trainer also pushes as tracer counters at each report
    #: (train/_trainer.py): what the dropless expert layers saw
    step_counters = (
        "moe.held_picks", "moe.picks", "moe.live_rows", "moe.buffer_rows", "moe.expert_load_max",
        "moe.expert_load_mean", "moe.pick_weight", "moe_aux_loss",
    )

    @staticmethod
    def _apply(model: TransformerLM, params: Any, inputs: jax.Array, **kw: Any) -> Tuple[Any, Dict[str, jax.Array]]:
        """``model.apply`` and, where the model has dropless experts, a
        step's expert load from what the layers ``sow`` (no second forward):
        picks that landed on a held expert, all picks, the rows of the buffer
        the kernels touch (held picks and each group's padding to a tile)
        beside all its rows, and the fullest held expert against the mean,
        over the layers; under the
        "mlp" router also the mean weight of a token's one pick (its
        probability: the router's gradient dies where it goes to 1 / experts or 1)."""
        if not model.cfg.moe_top_k:
            return model.apply(params, inputs, **kw), {}
        out, state = model.apply(params, inputs, mutable=["intermediates"], **kw)
        sown = jax.tree_util.tree_leaves_with_path(state["intermediates"])
        load, live_rows, buffer_rows = (
            jnp.stack([x for path, x in sown if name in jax.tree_util.keystr(path)]).astype(jnp.float32)
            for name in ("load", "live_rows", "buffer_rows")
        )
        picks = load.shape[0] * inputs.size * model.cfg.moe_top_k
        weight = [x for path, x in sown if "pick_weight" in jax.tree_util.keystr(path)]
        return out, {
            **({"moe.pick_weight": jnp.mean(jnp.stack(weight)).astype(jnp.float32)} if weight else {}),
            "moe.held_picks": jnp.sum(load),
            "moe.picks": jnp.asarray(picks, jnp.float32),
            "moe.live_rows": jnp.sum(live_rows),
            "moe.buffer_rows": jnp.sum(buffer_rows),
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
            q_rank = cfg.q_lora_rank or 0  # 0: queries straight from the stream
            attn = d * (q_rank + cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.n_heads * (
                (q_rank or d) * qk + cfg.kv_lora_rank * (cfg.qk_nope_head_dim + cfg.v_head_dim) + cfg.v_head_dim * d
            )
            width = cfg.n_heads * (qk + cfg.v_head_dim) // 2
        n_params, seen = cfg.vocab_size * d, 0
        outputs = cfg.moe_experts + cfg.moe_zero_experts
        router = d * outputs
        if cfg.moe_router == "mlp":
            r = cfg.router_hidden_size
            router = d * r + 2 * r * r + r * cfg.moe_experts
        for i in range(cfg.n_layers):
            if cfg.mixer_block and cfg.layer_type(i) != FULL:  # ONE mixer: no attention here, and no dense MLP anywhere
                if cfg.layer_type(i) == MAMBA2:
                    n_params += d * (2 * cfg.ssm_width + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads) + cfg.ssm_width * d
                else:
                    n_params += self._expert_layer_params(cfg, router)
                continue
            n_params += attn * cfg.attn_sublayers
            if cfg.layer_type(i) == CCA:  # the two convolutions' products
                n_params += (cfg.n_heads + cfg.kv_heads) * cfg.head_dim * (cfg.cca_time0 + cfg.cca_time1 * cfg.head_dim)
            if cfg.use_moe(i):
                n_params += self._expert_layer_params(cfg, router)
            if not cfg.mixer_block and (cfg.shortcut_block or not cfg.use_moe(i)):  # a shortcut block's two dense MLPs stand beside its experts
                n_params += cfg.attn_sublayers * 3 * d * cfg.ff_dim
            seen += cfg.attn_sublayers * min(cfg.window(cfg.layer_type(i)) or cfg.max_seq_len, cfg.max_seq_len)
        return float(6 * n_params + 12 * seen * width)

    @staticmethod
    def _expert_layer_params(cfg: TransformerConfig, router: int) -> float:
        """What a token multiplies with in an expert layer: the router, its
        expected picks among the held experts (at the latent width where the
        experts work in one, with the two projections round them), the shared experts."""
        d, width = cfg.d_model, cfg.moe_intermediate_size or cfg.ff_dim
        mats = 2 if cfg.moe_expert_act == "relu2" else 3  # an expert's matrices
        held = (cfg.moe_experts_held or (0, cfg.moe_experts))[1]
        active = cfg.moe_top_k * held / (cfg.moe_experts + cfg.moe_zero_experts) if cfg.moe_top_k else 2
        shared = cfg.moe_shared_intermediate_size or cfg.moe_shared_experts * width
        latent = 2 * d * cfg.moe_latent_size if cfg.moe_latent_size else 0
        return router + active * mats * (cfg.moe_latent_size or d) * width + latent + mats * d * shared

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
            fused = kernel_form.on_tpu()
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
