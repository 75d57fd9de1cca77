"""The adapter of a decoder with multi-head latent attention, a prefix of
dense layers and then, in every block, a sigmoid group-limited top-k router
over many experts of which one chip holds a range, beside a shared expert:
DeepSeek-V3, through the program's ``TransformerConfig`` (``kv_lora_rank`` ...,
``dense_prefix``, ``moe_router``, ``moe_shared_experts``, ``param_dtype``) and
``reference/deepseek_mla_moe.py``.

The configuration file keeps the source's key names.  ``n_routed_experts`` is
what THIS chip holds (a cut, under ``reduced``), from expert
``first_expert_held``; ``n_routed_experts_published`` is the router's width:
the program and the reference route over all of them, in their published
groups, and compute the held ones.  Not served: the multi-token-prediction
module (``num_nextn_predict_layers``; under ``deviations`` in the file).
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "deepseek_mla_moe")

init_params = dense.init_params
embedding_params = dense.embedding_params

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "softmax_scale",
    "dense_prefix", "moe_router", "moe_n_group", "moe_topk_group", "moe_routed_scaling", "moe_shared_experts",
    "param_dtype", "moe_top_k", "moe_experts_held", "rope_parameters",
)


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch deepseek_mla_moe: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "rms_norm_eps": 1e-6, "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu",
        "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc", "moe_layer_freq": 1,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if config["rope_scaling"]["type"] != "yarn" or int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("the program runs YaRN rotary and one key a head from the shared latent row")
    first, held, every = int(config["first_expert_held"]), int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    if not 0 <= first < first + held <= every or not 0 <= int(config["first_k_dense_replace"]) <= int(config["num_hidden_layers"]):
        raise ValueError("the held experts lie inside the published ones, and the dense prefix inside the layers")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["kv_cache"] != dtypes["compute"]:
        raise ValueError("the program serves float32 or bfloat16 parameters and caches in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def softmax_scale(config: Dict[str, Any]) -> float:
    """``(qk_nope + qk_rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    rs = config["rope_scaling"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
    return (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])) ** -0.5 * m * m


def rope_parameters(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published ``rope_scaling`` group as the program's ``rope_parameters``
    state one layer type's rotary: cos and sin carry ``mscale(mscale) /
    mscale(mscale_all_dim)``."""
    rs = config["rope_scaling"]
    ln = 0.1 * math.log(float(rs["factor"]))
    return {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": float(config["rope_theta"]), "factor": float(rs["factor"]),
            "original_max_position_embeddings": int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs["beta_fast"]), "beta_slow": float(rs["beta_slow"]),
            "attention_factor": (float(rs["mscale"]) * ln + 1.0) / (float(rs["mscale_all_dim"]) * ln + 1.0),
        }
    }


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "d_ff": int(config["intermediate_size"]),
        "rope_parameters": rope_parameters(config),
        "q_lora_rank": int(config["q_lora_rank"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "softmax_scale": softmax_scale(config),
        "dense_prefix": int(config["first_k_dense_replace"]),
        "moe_experts": int(config["n_routed_experts_published"]),
        "moe_every": 1,
        "moe_top_k": int(config["num_experts_per_tok"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"]),
        "moe_experts_held": [int(config["first_expert_held"]), int(config["n_routed_experts"])],
        "moe_router": "sigmoid_grouped",
        "moe_n_group": int(config["n_group"]),
        "moe_topk_group": int(config["topk_group"]),
        "moe_routed_scaling": float(config["routed_scaling_factor"]),
        "moe_shared_experts": int(config["n_shared_experts"]),
    }


def trial_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    return {}


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    h = trial_hparams(config)
    h["moe_experts_held"] = tuple(h["moe_experts_held"])
    return TransformerConfig(
        **h, max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]),
        param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down")


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layer = {"attn_norm": b["ln1"]["scale"], "mlp_norm": b["ln2"]["scale"], **{k: b["attn"][k] for k in _ATTN}}
        if "moe" in b:
            layer.update({k: b["moe"][k] for k in _MOE})
        else:
            layer.update({k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")})
        layers.append(layer)
    return {
        "embed": params["embed"]["embedding"],
        "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"],
        "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys."""
    return {
        "eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_scaling": config["rope_scaling"],
        "nope": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "latent": int(config["kv_lora_rank"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "scaling": float(config["routed_scaling_factor"]),
        "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.loss_and_logits(weights, tokens, **numerics(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The leaves one training step would be compared on (no cell trains
    this configuration yet): both ends of the latent bottlenecks, a router
    with its bias, a slice of every held expert, the shared expert."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq_a": first["wq_a"][:256],
        "first.wkv_a": first["wkv_a"][:256],
        "last.wkv_b": last["wkv_b"][:64],
        "last.wo": last["wo"][:8],
        "last.router": last["router"],
        "last.router_bias": last["router_bias"],
        "last.experts.w_gate": last["w_gate"][:, :64],
        "last.experts.w_down": last["w_down"][:, :32],
        "last.shared_w_up": last["shared_w_up"][:256],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def latent_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """Latent attention's sizes: what one cached row holds and what a query
    head multiplies with it."""
    return {
        "heads": int(config["num_attention_heads"]), "layers": int(config["num_hidden_layers"]),
        "kv_lora_rank": int(config["kv_lora_rank"]), "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]), "v_head_dim": int(config["v_head_dim"]),
        "q_lora_rank": int(config["q_lora_rank"]),
    }


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """As the equations are published (one key and value a head, expanded
    from the latent row): used by no metric of this configuration's cells,
    whose cost functions read ``latent_shape``."""
    s = latent_shape(config)
    return {
        "heads": s["heads"], "kv_heads": s["heads"], "head_dim": s["qk_nope_head_dim"] + s["qk_rope_head_dim"],
        "v_head_dim": s["v_head_dim"], "layers": s["layers"],
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert's two widths, how many are held and in how many layers, and
    how many of a token's picks land on a held one if the router spreads them evenly."""
    held, every = int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["moe_intermediate_size"]), "held": held,
        "layers": int(config["num_hidden_layers"]) - int(config["first_k_dense_replace"]),
        "shared": int(config["n_shared_experts"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's attention matrices (its two inner norms apart)."""
    s, d = latent_shape(config), int(config["hidden_size"])
    return (
        d * s["q_lora_rank"] + s["q_lora_rank"] * s["heads"] * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
        + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
        + s["kv_lora_rank"] * s["heads"] * (s["qk_nope_head_dim"] + s["v_head_dim"]) + s["heads"] * s["v_head_dim"] * d
    )


def _layers(config: Dict[str, Any], experts: float) -> float:
    """Matrix parameters of all layers, with ``experts`` routed experts
    counted in each expert layer (the router's bias rides with the router)."""
    e, d = expert_shape(config), int(config["hidden_size"])
    dense_layers = int(config["first_k_dense_replace"])
    one = 3 * d * e["d_ff"]
    sparse = d * int(config["n_routed_experts_published"]) + (e["shared"] + experts) * one
    return (dense_layers + e["layers"]) * attention_params(config) + dense_layers * 3 * d * int(config["intermediate_size"]) + e["layers"] * sparse


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with attention, and in a dense layer its MLP, in an
    expert layer the router, the shared expert and the experts it is expected
    to pick among the held ones; and the head."""
    return int(_layers(config, expert_shape(config)["expected_held_picks"])) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds."""
    e, s, d = expert_shape(config), latent_shape(config), int(config["hidden_size"])
    layers = int(config["num_hidden_layers"])
    norms = layers * (2 * d + s["q_lora_rank"] + s["kv_lora_rank"]) + d
    bias = e["layers"] * int(config["n_routed_experts_published"])
    return int(_layers(config, e["held"])) + norms + bias + 2 * embedding_params(config)
