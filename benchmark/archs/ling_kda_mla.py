"""The adapter of a decoder whose layers are Kimi-Delta-Attention mixers (a
delta-rule state whose decay is a value a CHANNEL) with, every
``layer_group_size``-th, a latent-attention layer without a query latent and
with a gate a head, a dense SwiGLU in the leading layers and DeepSeek-V3's
routed experts with one shared expert in the rest: Ling-3.0-flash's language
model (``model_type`` ``bailing_hybrid``), through the program's
``TransformerConfig`` (``layer_types`` of ``linear_attention`` /
``full_attention``, a decay a channel under ``linear_decay_floor``, the
latent sizes with ``q_lora_rank`` None, ``attn_output_gate``, the
``sigmoid_grouped`` router) and ``reference/ling_kda_mla.py``.

The configuration file keeps the source's key names.  ``num_experts`` is what
THIS chip holds (a cut, under ``reduced``), from expert ``first_expert_held``;
``num_experts_published`` is the router's width: the program and the reference
route over all of them and compute the held ones.  A serving request holds ONE
latent row a token in blocks for the latent layers AND a delta-rule state and a
convolution tail in its lane for the KDA layers.  Served only: the training
functions refuse by name (the file's ``deviations.training``).  Not built: the
vision tower and the multi-token-prediction module (``deviations``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "ling_kda_mla")

embedding_params = dense.embedding_params

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "layer_types", "linear_key_heads", "linear_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv",
    "linear_chunk", "linear_decay_floor", "attn_output_gate", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "dense_prefix", "moe_experts_held", "moe_top_k", "moe_router", "moe_n_group",
    "moe_topk_group", "moe_routed_scaling", "moe_shared_experts", "param_dtype", "head_dim", "norm_eps",
)
LINEAR, FULL = "linear_attention", "full_attention"
#: what the vision tower's keys would be called: a file that asks for one is refused
VISION = ("vision_config", "serve_vision", "image_tower", "mm_projector")


def pattern(config: Dict[str, Any]) -> List[str]:
    """The layers' types, in order: layer ``i`` is latent attention where ``(i + 1) % layer_group_size == 0``."""
    every = int(config["layer_group_size"])
    return [FULL if (i + 1) % every == 0 else LINEAR for i in range(int(config["num_hidden_layers"]))]


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models import transformer

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if lacks or LINEAR not in getattr(transformer, "LAYER_TYPES", ()):
        raise SpecError(
            f"arch ling_kda_mla: this program's TransformerConfig lacks {', '.join(sorted(lacks)) or 'the layer type linear_attention'}"
        )
    asked = [key for key in VISION if config.get(key)]
    if asked:
        raise ValueError(f"the vision tower is not built (deviations.vision_tower): the file asks for {', '.join(asked)}")
    must = {
        "q_lora_rank": None, "use_qk_norm": True, "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
        "norm_topk_prob": True, "linear_silu": True, "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
        "gated_attention_proj_granularity_type": "head_wise", "no_kda_lora": True, "use_kda_lora": False, "use_mla_nope": False,
        "use_nGPT": False, "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if not config["kda_safe_gate"]:
        other = config.get("kda_gate")
        raise ValueError(
            "kda_safe_gate false " + ("leaves the decay's gate unstated (`kda_gate` names the other)" if other is None else f"with the {other} gate")
            + ": the program runs the bounded gate alone (kda_safe_gate true under kda_lower_bound), whose floor the chunked form's scaling rests on"
        )
    layers = int(config["num_hidden_layers"])
    if int(config["layer_group_size"]) < 2 or FULL not in pattern(config):
        raise ValueError("layer_group_size >= 2 with at least one whole period: a period holds KDA layers and one latent layer")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        clamped = [i for i, limit in enumerate(config[key][:layers]) if limit]
        if clamped:
            raise ValueError(f"{key}: the kept layers {clamped} clamp their SwiGLU, which the program's experts do not run (layers 0-{layers - 1} are kept)")
    rotary = int(int(config["head_dim"]) * float(config["partial_rotary_factor"]))
    if not rotary == int(config["rotary_dim"]) == int(config["qk_rope_head_dim"]):
        raise ValueError("partial_rotary_factor x head_dim, rotary_dim and qk_rope_head_dim state ONE width three times")
    if int(config["head_dim"]) != int(config["v_head_dim"]) or int(config["qk_nope_head_dim"]) != int(config["head_dim"]):
        raise ValueError("a KDA head's keys and values and a latent head's nope keys and values are all head_dim wide")
    first, held, every = int(config["first_expert_held"]), int(config["num_experts"]), int(config["num_experts_published"])
    if not 0 <= first < first + held <= every:
        raise ValueError("the held experts lie inside the published ones")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["state"] != "float32" or (
        dtypes["kv_cache"] != dtypes["compute"]
    ):
        raise ValueError("the program serves float32 or bfloat16 parameters, keeps a float32 state and caches latent rows in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def _served_only(what: str):
    raise ValueError(
        f"arch ling_kda_mla is served only ({what}): trained at 16 B a parameter the least cut inside the floors is 884 M "
        "parameters, 14.1 GB of a chip's 16 before one activation (the file's deviations.training)"
    )


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    _served_only("trial_hparams")


def trial_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    _served_only("trial_overrides")


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    _served_only("reference_loss_and_logits")


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    _served_only("probe")


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    heads, width = int(config["num_attention_heads"]), int(config["head_dim"])
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]), n_layers=int(config["num_hidden_layers"]),
        n_heads=heads, head_dim=width, d_ff=int(config["intermediate_size"]), norm_eps=float(config["rms_norm_eps"]),
        layer_types=tuple(pattern(config)), rope_theta=float(config["rope_theta"]), attn_output_gate=True,
        linear_key_heads=heads, linear_value_heads=heads, linear_key_head_dim=width, linear_value_head_dim=width,
        linear_conv=int(config["short_conv_kernel_size"]), linear_decay_floor=float(config["kda_lower_bound"]),
        q_lora_rank=None, kv_lora_rank=int(config["kv_lora_rank"]), qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]), v_head_dim=int(config["v_head_dim"]),
        dense_prefix=int(config["first_k_dense_replace"]), moe_experts=int(config["num_experts_published"]), moe_every=1,
        moe_top_k=int(config["num_experts_per_tok"]), moe_intermediate_size=int(config["moe_intermediate_size"]),
        moe_experts_held=(int(config["first_expert_held"]), int(config["num_experts"])), moe_router="sigmoid_grouped",
        moe_n_group=int(config["n_group"]), moe_topk_group=int(config["topk_group"]),
        moe_routed_scaling=float(config["routed_scaling_factor"]), moe_shared_experts=1,
        moe_shared_intermediate_size=int(config["moe_shared_expert_intermediate_size"]),
        max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]), param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


#: the file's ``assumed.initialiser``: a channel remembers between this many tokens, log-uniform
REMEMBERS = (333.0, 53_333.0)


def slow_channels(key: Any, heads: int, channels: int, floor: float, dtype: Any) -> Dict[str, Any]:
    """``A_log`` 0 (a rate of one a head) and a ``dt_bias`` a channel such that a
    token whose projection ``a`` is 0 decays the channel by ``exp(-1 / tau)``,
    ``tau`` log-uniform in ``REMEMBERS``: ``floor * sigmoid(dt_bias) = -1 / tau``."""
    import jax
    import jax.numpy as jnp

    tau = jnp.exp(jax.random.uniform(key, (heads * channels,), jnp.float32, math.log(REMEMBERS[0]), math.log(REMEMBERS[1])))
    share = 1.0 / (-floor * tau)  # sigmoid(dt_bias)
    return {"A_log": jnp.zeros((heads,), dtype), "dt_bias": (jnp.log(share) - jnp.log1p(-share)).astype(dtype)}


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call from
    the seed, in the dtype it serves them in.  In that same call each KDA
    layer's ``A_log`` and ``dt_bias`` are drawn by ``slow_channels``, each expert
    layer's three stacks of held experts are multiplied by ``sqrt(held)`` (the
    program draws a stack at a fan-in of ``held x in``: Qwen3-Next's adapter
    says why) and its selection bias is drawn normal(0.02) (zeros would leave
    the bias's part in the picks untested)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        params = meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]
        of_channels, of_bias = jax.random.fold_in(key, 0x6DA), jax.random.fold_in(key, 0xB1A5)
        whole = lambda w: (w.astype(jnp.float32) * w.shape[0] ** 0.5).astype(w.dtype)  # noqa: E731
        for name, blk in params.items():
            if "gdn" in blk:
                drawn = slow_channels(
                    jax.random.fold_in(of_channels, int(name[6:])), model_cfg.linear_value_heads, model_cfg.linear_key_head_dim,
                    model_cfg.linear_decay_floor, blk["gdn"]["A_log"].dtype,
                )
                blk = dict(blk, gdn=dict(blk["gdn"], **drawn))
            if "moe" in blk:
                bias = blk["moe"]["router_bias"]
                drawn = 0.02 * jax.random.normal(jax.random.fold_in(of_bias, int(name[6:])), bias.shape, jnp.float32)
                blk = dict(blk, moe=dict(blk["moe"], router_bias=drawn.astype(bias.dtype), **{n: whole(blk["moe"][n]) for n in ("w_gate", "w_up", "w_down")}))
            params = dict(params, **{name: blk})
        return params

    return make(jax.random.key(model.seed32(seed)))


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down")
#: the program's leaf -> the reference's name
_KDA = {"w_in": "w_in", "w_ba": "w_b", "w_decay": "w_decay", "conv_w": "conv_w", "dt_bias": "dt_bias", "A_log": "A_log", "norm": "kda_norm", "w_out": "w_out"}
_LATENT = {"wq": "wq", "wkv_a": "wkv_a", "kv_norm": "kv_norm", "wkv_b": "wkv_b", "w_gate": "w_head_gate", "wo": "wo"}


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i, kind in enumerate(pattern(config)):
        b = params[f"block_{i}"]
        names, leaves = (_KDA, b["gdn"]) if kind == LINEAR else (_LATENT, b["attn"])
        ffn = {k: b["moe"][k] for k in _MOE} if "moe" in b else {k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")}
        layers.append({"mixer_norm": b["ln1"]["scale"], "ffn_norm": b["ln2"]["scale"], **{to: leaves[of] for of, to in names.items()}, **ffn})
    return {
        "embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"], "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys and this chip's share."""
    return {
        "eps": float(config["rms_norm_eps"]), "rope_theta": float(config["rope_theta"]), "heads": int(config["num_attention_heads"]),
        "key_dim": int(config["head_dim"]), "conv": int(config["short_conv_kernel_size"]), "lower_bound": float(config["kda_lower_bound"]),
        "nope": int(config["qk_nope_head_dim"]), "latent": int(config["kv_lora_rank"]), "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]), "topk_group": int(config["topk_group"]), "scaling": float(config["routed_scaling_factor"]),
        "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each mixer this file runs, how many hold a dense SwiGLU and how many experts."""
    kinds, layers, prefix = pattern(config), int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    return {LINEAR: kinds.count(LINEAR), FULL: kinds.count(FULL), "dense": min(prefix, layers), "experts": max(layers - prefix, 0)}


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The latent layers alone: only they keep a row a token (one for all heads)."""
    return {
        "heads": int(config["num_attention_heads"]), "kv_heads": 1, "layers": layer_counts(config)[FULL],
        "head_dim": int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"]),
        "latent": int(config["kv_lora_rank"]), "rope": int(config["qk_rope_head_dim"]), "v_head_dim": int(config["v_head_dim"]),
    }


def latent_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What ``costs/mla_paged_attention`` asks: the cached row, the heads that read it, the layers that keep one."""
    return {
        "kv_lora_rank": int(config["kv_lora_rank"]), "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "heads": int(config["num_attention_heads"]), "layers": layer_counts(config)[FULL],
    }


def kda_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What a request holds of one KDA layer, whatever its length: a head's state is ``K x V`` float32 values."""
    h, d = int(config["num_attention_heads"]), int(config["head_dim"])
    return {
        "heads": h, "key_dim": d, "value_dim": d, "conv": int(config["short_conv_kernel_size"]), "channels": 3 * h * d,
        "layers": layer_counts(config)[LINEAR], "bytes_per_slot": h * d * d * 4,
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert as it is held (three matrices of ``d_model x d_ff``), how many
    are held and in how many layers, and how many of a token's picks land on a
    held one if the router spreads them evenly."""
    held, every = int(config["num_experts"]), int(config["num_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["moe_intermediate_size"]), "matrices": 3,
        "held": held, "layers": layer_counts(config)["experts"], "shared_d_ff": int(config["moe_shared_expert_intermediate_size"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A KDA layer's mixer: the in-projection (q, k, v and the output gate), the
    decay's full projection and ``dt_bias``, beta's, the convolution, ``A_log``,
    the gated norm, the out-projection."""
    d, s = int(config["hidden_size"]), kda_shape(config)
    width = s["heads"] * s["key_dim"]
    return {
        "w_in": d * 4 * width, "w_decay": d * width, "w_b": d * s["heads"], "conv": s["conv"] * s["channels"],
        "dt_bias": width, "A_log": s["heads"], "norm": s["value_dim"], "w_out": width * d,
    }


def attention_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A latent layer's mixer: ``wq`` straight from the stream, ``wkv_a``, its norm, ``wkv_b``, the gate a head, ``wo``."""
    d, a = int(config["hidden_size"]), attention_shape(config)
    return {
        "wq": d * a["heads"] * a["head_dim"], "wkv_a": d * (a["latent"] + a["rope"]), "kv_norm": a["latent"],
        "wkv_b": a["latent"] * a["heads"] * (int(config["qk_nope_head_dim"]) + a["v_head_dim"]), "w_gate": d * a["heads"],
        "wo": a["heads"] * a["v_head_dim"] * d,
    }


def ffn_params(config: Dict[str, Any]) -> Dict[str, int]:
    """What a layer holds after its mixer: a dense layer's SwiGLU, or an expert
    layer's router with its bias, shared expert and ONE routed expert (``expert``)."""
    d, e = int(config["hidden_size"]), expert_shape(config)
    return {
        "dense": 3 * d * int(config["intermediate_size"]), "router": d * int(config["num_experts_published"]),
        "router_bias": int(config["num_experts_published"]), "shared": 3 * d * e["shared_d_ff"], "expert": 3 * d * e["d_ff"],
    }


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the mixers, what follows them, two norms a layer, both tables, the final norm."""
    n, f, d = layer_counts(config), ffn_params(config), int(config["hidden_size"])
    mixers = n[LINEAR] * sum(mixer_params(config).values()) + n[FULL] * sum(attention_params(config).values())
    experts = n["experts"] * (f["router"] + f["router_bias"] + f["shared"] + int(config["num_experts"]) * f["expert"])
    return mixers + n["dense"] * f["dense"] + experts + (n[LINEAR] + n[FULL]) * 2 * d + 2 * embedding_params(config) + d


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters in a matrix multiplication for every token: a KDA layer's four
    projections, a latent layer's five, a dense layer's SwiGLU, an expert layer's
    router, shared expert and the token's expected held picks, and the head
    (the embedding is a lookup; norms, biases, the convolution and the rule no
    product with a matrix)."""
    n, m, a, f = layer_counts(config), mixer_params(config), attention_params(config), ffn_params(config)
    mixers = n[LINEAR] * (m["w_in"] + m["w_decay"] + m["w_b"] + m["w_out"]) + n[FULL] * (sum(a.values()) - a["kv_norm"])
    experts = f["router"] + f["shared"] + expert_shape(config)["expected_held_picks"] * f["expert"]
    return mixers + n["dense"] * f["dense"] + n["experts"] * experts + embedding_params(config)
