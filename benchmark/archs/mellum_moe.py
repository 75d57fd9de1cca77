"""The adapter of a decoder with mixed window / full attention layers, a
rotary section a layer type (YaRN) and dropless top-k experts in every block
of which one chip holds a range: Mellum2-12B-A2.5B, through the program's
``TransformerConfig`` (``layer_types``, ``rope_parameters``, ``moe_top_k``,
``moe_experts_held``) and ``reference/mellum_moe.py``.

The configuration file keeps the source's key names.  ``num_experts`` is what
THIS chip holds (a cut, under ``reduced``), from expert
``first_expert_held``; ``num_experts_published`` is the router's width: the
program and the reference route over all of them and compute the held ones.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "mellum_moe")

init_params = dense.init_params
attention_shape = dense.attention_shape
embedding_params = dense.embedding_params


#: what the program's model config has to know before this adapter can hand it a file
NEEDS = ("head_dim", "layer_types", "sliding_window", "rope_parameters", "moe_top_k", "moe_intermediate_size", "moe_experts_held")


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states (``LMTrial`` takes
    hparams it does not know in silence, and would run another model)."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch mellum_moe: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "rms_norm_eps": 1e-6, "attention_bias": False, "tie_word_embeddings": False,
        "hidden_act": "silu", "norm_topk_prob": True,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    layers = int(config["num_hidden_layers"])
    if len(config["layer_types"]) != layers or set(config["mlp_layer_types"]) != {"sparse"} or len(config["mlp_layer_types"]) != layers:
        raise ValueError("layer_types and mlp_layer_types name every layer, and every block is sparse")
    first, held, every = int(config["first_expert_held"]), int(config["num_experts"]), int(config["num_experts_published"])
    if not 0 <= first < first + held <= every or int(config["num_experts_per_tok"]) > every:
        raise ValueError("the held experts lie inside the published ones, and top-k within them")
    if config["dtypes"]["compute"] not in ("float32", "bfloat16") or config["dtypes"]["params"] != "float32":
        raise ValueError("the program trains float32 parameters; compute is float32 or bfloat16")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "d_ff": int(config["intermediate_size"]),  # no block is dense: unused, as in the source
        "layer_types": list(config["layer_types"]),
        "sliding_window": int(config["sliding_window"]),
        "rope_parameters": config["rope_parameters"],
        "moe_experts": int(config["num_experts_published"]),
        "moe_every": 1,
        "moe_top_k": int(config["num_experts_per_tok"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"]),
        "moe_experts_held": [int(config["first_expert_held"]), int(config["num_experts"])],
        "moe_aux_weight": float(config["assumed"]["moe_aux_weight"]["value"]),
    }


def trial_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    return {}


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    h = trial_hparams(config)
    return TransformerConfig(
        vocab_size=h["vocab_size"], d_model=h["d_model"], n_layers=h["n_layers"], n_heads=h["n_heads"],
        n_kv_heads=h["n_kv_heads"], head_dim=h["head_dim"], d_ff=h["d_ff"], max_seq_len=int(max_seq_len),
        layer_types=tuple(h["layer_types"]), sliding_window=h["sliding_window"],
        rope_parameters=h["rope_parameters"], moe_experts=h["moe_experts"], moe_every=1,
        moe_top_k=h["moe_top_k"], moe_intermediate_size=h["moe_intermediate_size"],
        moe_experts_held=tuple(h["moe_experts_held"]), moe_aux_weight=h["moe_aux_weight"],
        dtype=jnp.dtype(config["dtypes"]["compute"]),
    )


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views)."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layers.append(
            {
                "attn_norm": b["ln1"]["scale"],
                **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
                "mlp_norm": b["ln2"]["scale"],
                **{k: b["moe"][k] for k in ("router", "w_gate", "w_up", "w_down")},
            }
        )
    return {
        "embed": params["embed"]["embedding"],
        "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"],
        "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration."""
    return {
        "layer_types": list(config["layer_types"]),
        "window": int(config["sliding_window"]),
        "rope_parameters": config["rope_parameters"],
        "eps": float(config["rms_norm_eps"]),
        "top_k": int(config["num_experts_per_tok"]),
        "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))[0]


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    """Cross-entropy + the assumed coefficient x the layers' auxiliary
    losses: what ``LMTrial.loss`` returns for this model."""
    return reference.loss_and_logits(
        weights, tokens, aux_weight=float(config["assumed"]["moe_aux_weight"]["value"]), **numerics(config)
    )


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """A sliding layer's and the full layer's ``wq``, both routers, a slice
    of EVERY held expert's three matrices in the last layer, and the dense
    decoder's leaves outside the MLP.  Not one expert's matrices whole: after
    a few hundred updates a router starves some experts, and a leaf whose
    reference gradient is a handful of tokens' (or none's) turns one flipped
    pick into a relative error of 0.7 and more (my chip runs, PR 27)."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:256],
        "first.router": first["router"],
        "last.wq": last["wq"][:256],
        "last.router": last["router"],
        "last.wo": last["wo"][:8],
        "last.experts.w_gate": last["w_gate"][:, :64],
        "last.experts.w_up": last["w_up"][:, :64],
        "last.experts.w_down": last["w_down"][:, :32],
        "last.mlp_norm": last["mlp_norm"],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def layer_windows(config: Dict[str, Any]) -> List[Optional[int]]:
    """A layer's window, or None where it sees every earlier key."""
    return [int(config["sliding_window"]) if t == "sliding_attention" else None for t in config["layer_types"]]


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert's two widths, how many are held, and how many of a token's
    picks land on a held one if the router spreads them evenly."""
    held, every = int(config["num_experts"]), int(config["num_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["moe_intermediate_size"]), "held": held,
        "layers": int(config["num_hidden_layers"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def _per_layer(config: Dict[str, Any], experts: float) -> float:
    s, e = attention_shape(config), expert_shape(config)
    attn = e["d_model"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    router = e["d_model"] * int(config["num_experts_published"])
    return attn + router + experts * 3 * e["d_model"] * e["d_ff"]


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with attention, the router, the experts it is
    expected to pick among the held ones, and the head."""
    e = expert_shape(config)
    return int(e["layers"] * _per_layer(config, e["expected_held_picks"])) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds."""
    e = expert_shape(config)
    return int(e["layers"] * (_per_layer(config, e["held"]) + 2 * e["d_model"])) + e["d_model"] + 2 * embedding_params(config)
