"""The adapter of LongCat-Flash's decoder (the language model of
LongCat-Flash-Omni): in every block two latent-attention sublayers and two
dense FFNs round a shortcut-connected expert branch, a softmax router with a
selection bias over ``n_routed_experts`` real and ``zero_expert_num`` identity
experts, top-``moe_topk`` without renormalisation, a scale on both latents;
through the program's ``TransformerConfig`` (``shortcut_block``,
``moe_zero_experts``, ``moe_router`` "softmax_bias", ``q_latent_scale``,
``kv_latent_scale``) and ``reference/longcat_scmoe.py``.

The configuration file keeps the source's key names.  ``n_routed_experts`` is
what THIS chip holds of the real experts (a cut, under ``reduced``), from
expert ``first_expert_held``; ``n_routed_experts_published`` is how many real
experts the router scores: program and reference route over all
``n_routed_experts_published + zero_expert_num`` outputs and compute the held
ones and the identity experts.  Not built: the audio and vision encoders and
the codec decoder of the Omni model (no key of the file describes them).
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "longcat_scmoe")

init_params = dense.init_params
embedding_params = dense.embedding_params

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "shortcut_block", "moe_zero_experts", "q_latent_scale", "kv_latent_scale", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_router", "moe_routed_scaling", "moe_top_k",
    "moe_experts_held", "moe_intermediate_size", "param_dtype", "norm_eps",
)


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch longcat_scmoe: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "zero_expert_type": "identity", "attention_method": "MLA", "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "attention_bias": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if config.get("rope_scaling") is not None or config.get("tie_word_embeddings", False):
        raise ValueError("the program runs this architecture with plain rotary embeddings (no rope_scaling) and an untied head")
    first, held, every = int(config["first_expert_held"]), int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    if not 0 <= first < first + held <= every:
        raise ValueError(f"the held experts {first}..{first + held - 1} lie inside the {every} real ones: an identity expert is held by nobody")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["kv_cache"] != dtypes["compute"]:
        raise ValueError("the program serves float32 or bfloat16 parameters and caches in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def latent_scales(config: Dict[str, Any]) -> Dict[str, float]:
    """``(hidden_size / rank) ** 0.5`` on each normed latent, where the file's boolean says so (the report's values)."""
    d = int(config["hidden_size"])
    return {
        "q_latent_scale": (d / int(config["q_lora_rank"])) ** 0.5 if config["mla_scale_q_lora"] else 1.0,
        "kv_latent_scale": (d / int(config["kv_lora_rank"])) ** 0.5 if config["mla_scale_kv_lora"] else 1.0,
    }


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "d_ff": int(config["ffn_hidden_size"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "shortcut_block": True,
        "q_lora_rank": int(config["q_lora_rank"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        **latent_scales(config),
        "moe_experts": int(config["n_routed_experts_published"]),
        "moe_zero_experts": int(config["zero_expert_num"]),
        "moe_every": 1,
        "moe_top_k": int(config["moe_topk"]),
        "moe_intermediate_size": int(config["expert_ffn_hidden_size"]),
        "moe_experts_held": [int(config["first_expert_held"]), int(config["n_routed_experts"])],
        "moe_router": "softmax_bias",
        "moe_routed_scaling": float(config["routed_scaling_factor"]),
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
_FFN = ("w_gate", "w_up", "w_down")


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i in range(int(config["num_layers"])):
        b = params[f"block_{i}"]
        sub = [
            {"attn_norm": b["ln1" + t]["scale"], "ffn_norm": b["ln2" + t]["scale"], **{k: b["attn" + t][k] for k in _ATTN},
             **{k: b["mlp" + t][k]["kernel"] for k in _FFN}}
            for t in ("", "_1")
        ]
        moe = b["moe"]
        layers.append({
            "sub": sub, "router": moe["router"], "router_bias": moe["router_bias"],
            "e_gate": moe["w_gate"], "e_up": moe["w_up"], "e_down": moe["w_down"],
        })
    return {
        "embed": params["embed"]["embedding"],
        "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"],
        "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys."""
    scales = latent_scales(config)
    return {
        "eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]),
        "nope": int(config["qk_nope_head_dim"]),
        "latent": int(config["kv_lora_rank"]),
        "q_scale": scales["q_latent_scale"],
        "kv_scale": scales["kv_latent_scale"],
        "top_k": int(config["moe_topk"]),
        "scaling": float(config["routed_scaling_factor"]),
        "real_experts": int(config["n_routed_experts_published"]),
        "first_expert": int(config["first_expert_held"]),
        "held": int(config["n_routed_experts"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.loss_and_logits(weights, tokens, **numerics(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The leaves one training step would be compared on (no cell trains this
    configuration yet): both sublayers' latent bottlenecks and dense FFNs, a
    router with its bias, a slice of every held expert."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.sub0.wq_a": first["sub"][0]["wq_a"][:256],
        "first.sub1.wkv_a": first["sub"][1]["wkv_a"][:256],
        "first.sub0.w_gate": first["sub"][0]["w_gate"][:256],
        "last.sub1.wkv_b": last["sub"][1]["wkv_b"][:64],
        "last.sub1.wo": last["sub"][1]["wo"][:8],
        "last.sub1.w_down": last["sub"][1]["w_down"][:256],
        "last.router": last["router"],
        "last.router_bias": last["router_bias"],
        "last.experts.w_gate": last["e_gate"][:, :64],
        "last.experts.w_down": last["e_down"][:, :32],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------

#: attention sublayers (cached rows a token) and dense FFNs a block
SUBLAYERS = 2


def latent_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """Latent attention's sizes: what one cached row holds and what a query
    head multiplies with it; ``layers`` counts the cached SUBLAYERS, two a block."""
    return {
        "heads": int(config["num_attention_heads"]), "layers": SUBLAYERS * int(config["num_layers"]),
        "kv_lora_rank": int(config["kv_lora_rank"]), "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]), "v_head_dim": int(config["v_head_dim"]),
        "q_lora_rank": int(config["q_lora_rank"]),
    }


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """As the equations are published (one key and value a head, expanded
    from the latent row): used by no metric of this configuration's cell,
    whose cost functions read ``latent_shape``."""
    s = latent_shape(config)
    return {
        "heads": s["heads"], "kv_heads": s["heads"], "head_dim": s["qk_nope_head_dim"] + s["qk_rope_head_dim"],
        "v_head_dim": s["v_head_dim"], "layers": s["layers"],
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert's two widths, how many are held and in how many layers, how
    many of a token's picks land on a held one and how many on an identity
    expert if the router spreads them evenly over its outputs."""
    held, zero = int(config["n_routed_experts"]), int(config["zero_expert_num"])
    outputs, k = int(config["n_routed_experts_published"]) + zero, int(config["moe_topk"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["expert_ffn_hidden_size"]), "held": held,
        "layers": int(config["num_layers"]), "shared": 0, "expected_held_picks": k * held / outputs,
        "zero": zero, "expected_zero_picks": k * zero / outputs,
    }


def attention_params(config: Dict[str, Any]) -> int:
    """One sublayer's attention matrices (its two inner norms apart)."""
    s, d = latent_shape(config), int(config["hidden_size"])
    return (
        d * s["q_lora_rank"] + s["q_lora_rank"] * s["heads"] * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
        + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
        + s["kv_lora_rank"] * s["heads"] * (s["qk_nope_head_dim"] + s["v_head_dim"]) + s["heads"] * s["v_head_dim"] * d
    )


def _layers(config: Dict[str, Any], experts: float) -> float:
    """Matrix parameters of all blocks with ``experts`` routed experts counted
    in each: two attention sublayers, two dense FFNs, the router."""
    e, d = expert_shape(config), int(config["hidden_size"])
    outputs = int(config["n_routed_experts_published"]) + e["zero"]
    one = SUBLAYERS * (attention_params(config) + 3 * d * int(config["ffn_hidden_size"])) + d * outputs + experts * 3 * d * e["d_ff"]
    return e["layers"] * one


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with both attention sublayers, both dense FFNs, the
    router and the experts it is expected to pick among the held ones (an
    identity expert multiplies with no matrix); and the head."""
    return int(_layers(config, expert_shape(config)["expected_held_picks"])) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds."""
    e, s, d = expert_shape(config), latent_shape(config), int(config["hidden_size"])
    norms = e["layers"] * SUBLAYERS * (2 * d + s["q_lora_rank"] + s["kv_lora_rank"]) + d
    bias = e["layers"] * (int(config["n_routed_experts_published"]) + e["zero"])
    return int(_layers(config, e["held"])) + norms + bias + 2 * embedding_params(config)
