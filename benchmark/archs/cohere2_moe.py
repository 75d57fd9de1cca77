"""The adapter of a decoder whose block is parallel (attention and experts
read ONE LayerNorm), whose layers alternate three sliding-window layers with
rotary positions and one full layer without any, with a plain sigmoid top-k
router over many experts of which one chip holds a range, beside shared
experts that are averaged, and a tied head: Command A+ (``cohere2_moe``),
through the program's ``TransformerConfig`` (``norm``, ``parallel_block``,
``rope_parameters`` with ``rope_type`` none, ``tie_embeddings``,
``moe_router`` sigmoid, ``moe_shared_combine``) and
``reference/cohere2_moe.py``.

The configuration file keeps the source's key names.  ``num_experts`` is what
THIS chip holds (a cut, under ``reduced``), from expert ``first_expert_held``;
``num_experts_published`` is the router's width: the program and the reference
route over all of them and compute the held ones.  Not served: the vision
tower (under ``deviations`` in the file).
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "cohere2_moe")

init_params = dense.init_params
embedding_params = dense.embedding_params
attention_shape = dense.attention_shape

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "norm", "norm_eps", "parallel_block", "tie_embeddings", "logit_scale", "moe_shared_combine", "moe_shared_experts",
    "moe_router", "moe_top_k", "moe_experts_held", "layer_types", "sliding_window", "rope_parameters", "param_dtype",
)
SLIDING, FULL = "sliding_attention", "full_attention"


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch cohere2_moe: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "attention_bias": False, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0, "hidden_act": "silu",
        "norm_topk_prob": True, "position_embedding_type": "rope_gptj", "rotary_pct": 1, "rms_norm_eps": None,
        "shared_expert_combination_strategy": "average", "tie_word_embeddings": True, "use_gated_activation": True,
        "use_parallel_block": True, "use_qk_norm": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    layers = int(config["num_hidden_layers"])
    if len(config["layer_types"]) != layers or set(config["layer_types"]) - {SLIDING, FULL}:
        raise ValueError("layer_types names every layer as sliding_attention or full_attention")
    first, held, every = int(config["first_expert_held"]), int(config["num_experts"]), int(config["num_experts_published"])
    if not 0 <= first < first + held <= every or int(config["num_experts_per_tok"]) > every:
        raise ValueError("the held experts lie inside the published ones, and top-k within them")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["kv_cache"] != dtypes["compute"]:
        raise ValueError("the program serves float32 or bfloat16 parameters and caches in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def rope_parameters(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published rotary group on the window layers; none on the full ones."""
    published = config["rope_parameters"]
    return {
        SLIDING: {"rope_type": str(published["rope_type"]), "rope_theta": float(published["rope_theta"])},
        FULL: {"rope_type": "none"},
    }


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "d_ff": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "layer_types": list(config["layer_types"]),
        "sliding_window": int(config["sliding_window"]),
        "rope_parameters": rope_parameters(config),
        "norm": "layernorm",
        "norm_eps": float(config["layer_norm_eps"]),
        "parallel_block": True,
        "tie_embeddings": True,
        "logit_scale": float(config["logit_scale"]),
        "moe_experts": int(config["num_experts_published"]),
        "moe_every": 1,
        "moe_top_k": int(config["num_experts_per_tok"]),
        "moe_intermediate_size": int(config["intermediate_size"]),
        "moe_experts_held": [int(config["first_expert_held"]), int(config["num_experts"])],
        "moe_router": "sigmoid",
        "moe_shared_experts": int(config["num_shared_experts"]),
        "moe_shared_combine": "mean",
    }


def trial_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    return {}


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    h = trial_hparams(config)
    h["moe_experts_held"], h["layer_types"] = tuple(h["moe_experts_held"]), tuple(h["layer_types"])
    return TransformerConfig(
        **h, max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]),
        param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_MOE = ("router", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down")


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layers.append({
            "norm": b["ln1"]["scale"], **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
            **{k: b["moe"][k] for k in _MOE},
        })
    return {"embed": params["embed"]["embedding"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys."""
    return {
        "eps": float(config["layer_norm_eps"]),
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "window": int(config["sliding_window"]),
        "layer_types": tuple(config["layer_types"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["num_shared_experts"]),
        "first_expert": int(config["first_expert_held"]),
        "logit_scale": float(config["logit_scale"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.loss_and_logits(weights, tokens, **numerics(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The leaves one training step would be compared on (no cell trains
    this configuration): the tied table's rows, a window and the full layer's
    projections, a router, a slice of every held expert, the shared experts."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:64],
        "first.wk": first["wk"][:256],
        "last.wo": last["wo"][:8],
        "last.norm": last["norm"],
        "last.router": last["router"],
        "last.experts.w_gate": last["w_gate"][:, :64],
        "last.experts.w_down": last["w_down"][:, :32],
        "last.shared_w_up": last["shared_w_up"][:256],
        "final_norm": weights["final_norm"],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def window_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The two kinds of layer and what a cached token costs in either."""
    types = list(config["layer_types"])
    return {
        "window": int(config["sliding_window"]), "window_layers": types.count(SLIDING), "full_layers": types.count(FULL),
        "heads": int(config["num_attention_heads"]), "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert's two widths, how many are held and in how many layers, and
    how many of a token's picks land on a held one if the router spreads them evenly."""
    held, every = int(config["num_experts"]), int(config["num_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["intermediate_size"]), "held": held,
        "layers": int(config["num_hidden_layers"]), "shared": int(config["num_shared_experts"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's four attention matrices."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    return 2 * d * hd * (int(config["num_attention_heads"]) + int(config["num_key_value_heads"]))


def _layers(config: Dict[str, Any], experts: float) -> float:
    """Matrix parameters of all layers, with ``experts`` routed experts counted in each."""
    e, d = expert_shape(config), int(config["hidden_size"])
    one = 3 * d * e["d_ff"]
    return e["layers"] * (attention_params(config) + d * int(config["num_experts_published"]) + (e["shared"] + experts) * one)


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with attention, the router, the shared experts and
    the experts it is expected to pick among the held ones; and the tied head."""
    return int(_layers(config, expert_shape(config)["expected_held_picks"])) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the tied table counts once."""
    d = int(config["hidden_size"])
    norms = (int(config["num_hidden_layers"]) + 1) * d
    return int(_layers(config, expert_shape(config)["held"])) + norms + embedding_params(config)
