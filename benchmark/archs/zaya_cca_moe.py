"""The adapter of a decoder whose every layer is compressed convolutional
attention (CCA) and a top-1 expert sublayer routed by an MLP that carries a
state from layer to layer, both merged into the stream by learned residual
scaling, under a tied head: ZAYA1-8B, through the program's
``TransformerConfig`` (layer type ``cca``, ``cca_time0``, ``cca_time1``,
``partial_rotary_factor``, ``moe_router`` ``mlp``, ``router_hidden_size``,
``residual_scaling``, ``tie_embeddings``, ``moe_experts_held``) and
``reference/zaya_cca_moe.py``.  Trained, not served: the program has no cache
kind for a CCA layer, and ``model_config`` is what the adapter's tests build.

The configuration file keeps the source's key names.  ``num_experts`` is what
THIS chip holds (a cut, under ``reduced``), from expert
``first_expert_held``; ``num_experts_published`` is the router's width: the
program and the reference route over all of them and compute the held ones.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "zaya_cca_moe")

init_params = dense.init_params
attention_shape = dense.attention_shape
embedding_params = dense.embedding_params


#: what the program's model config has to know before this adapter can hand it a file
NEEDS = ("cca_time0", "cca_time1", "partial_rotary_factor", "router_hidden_size", "residual_scaling", "tie_embeddings", "moe_experts_held")
#: the published name of a layer of one CCA sublayer and one expert sublayer, and the program's
PUBLISHED_LAYER, PROGRAM_LAYER = "hybrid", "cca"


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states (``LMTrial`` takes
    hparams it does not know in silence, and would run another model)."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch zaya_cca_moe: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "attention_bias": False, "lm_head_bias": False, "tie_word_embeddings": True, "hidden_act": "silu",
        "sliding_window": None, "num_experts_per_tok": 1,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if set(config["layer_types"]) != {PUBLISHED_LAYER} or len(config["layer_types"]) != int(config["num_hidden_layers"]):
        raise ValueError(f"layer_types names every layer, and every layer is {PUBLISHED_LAYER!r} (no window layer)")
    if any(key.startswith("zaya_") or "skip" in key for key in config):
        raise ValueError("no skip expert (the sibling rows' zaya_use_mod) is run: the file may not state one")
    first, held, every = int(config["first_expert_held"]), int(config["num_experts"]), int(config["num_experts_published"])
    if not 0 <= first < first + held <= every:
        raise ValueError("the held experts lie inside the published ones")
    dtypes = config["dtypes"]
    if dtypes["compute"] not in ("float32", "bfloat16") or dtypes["params"] != "float32":
        raise ValueError("the program trains float32 parameters; compute is float32 or bfloat16")
    if "serve_params" in dtypes or "kv_cache" in dtypes:
        raise ValueError("a CCA layer is not served: the file may state no serving dtypes")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def _rope(config: Dict[str, Any]) -> Dict[str, Any]:
    return config["rope_parameters"][PUBLISHED_LAYER]


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layer_types": [PROGRAM_LAYER] * int(config["num_hidden_layers"]),
        "cca_time0": int(config["cca_time0"]),
        "cca_time1": int(config["cca_time1"]),
        "partial_rotary_factor": float(_rope(config)["partial_rotary_factor"]),
        "rope_theta": float(_rope(config)["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "residual_scaling": True,
        "tie_embeddings": True,
        "moe_experts": int(config["num_experts_published"]),
        "moe_every": 1,
        "moe_top_k": 1,
        "moe_router": "mlp",
        "router_hidden_size": int(config["router_hidden_size"]),
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
    h["layer_types"], h["moe_experts_held"] = tuple(h["layer_types"]), tuple(h["moe_experts_held"])
    return TransformerConfig(max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]), **h)


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_SCALING = ("res_bias", "res_scale", "out_bias", "out_scale")
#: the reference's name of a router leaf -> the program's
_ROUTER = {
    "down": "router_down", "down_bias": "router_down_bias", "mix": "router_mix", "norm": "router_norm",
    "w1": "router_w1", "b1": "router_b1", "w2": "router_w2", "b2": "router_b2", "w3": "router_w3", "beta": "router_bias",
}


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names: views, and
    the two convolutions turned into the reference's ``[channel, ..., tap]``."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        a, moe = b["attn"], b["moe"]
        taps0, heads, hd = a["conv0_w"].shape
        layers.append(
            {
                "attn_norm": b["ln1"]["scale"],
                **{k: a[k]["kernel"] for k in ("wq", "wk", "wv1", "wv2", "wo")},
                "tau": a["tau"],
                "conv0": a["conv0_w"].reshape(taps0, heads * hd).T,
                "conv0_bias": a["conv0_b"].reshape(heads * hd),
                # the program's [tap, head, c', c] -> B[(head, c), c', tap]
                "conv1": a["conv1_w"].transpose(1, 3, 2, 0).reshape(heads * hd, hd, -1),
                "conv1_bias": a["conv1_b"].reshape(heads * hd),
                "attn_scaling": {k: b["rescale1"][k] for k in _SCALING},
                "mlp_norm": b["ln2"]["scale"],
                "router": {ours: moe[theirs] for ours, theirs in _ROUTER.items()},
                **{k: moe[k] for k in ("w_gate", "w_up", "w_down")},
                "mlp_scaling": {k: b["rescale2"][k] for k in _SCALING},
            }
        )
    return {"embed": params["embed"]["embedding"], "final_norm": params["ln_f"]["scale"], "layers": layers}


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration."""
    return {
        "rotary": int(int(config["head_dim"]) * float(_rope(config)["partial_rotary_factor"])),
        "rope_theta": float(_rope(config)["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
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
    """Of a layer past the first (whose router takes the state handed on):
    CCA's ``wq``, the convolution that mixes a head's channels, the keys'
    temperature, the shifted value's ``wv2``, the router's down-projection,
    last product and the state's mix, a residual scale; of the last layer a
    slice of EVERY held expert's three matrices (one starved expert's whole
    matrix reads a handful of tokens' gradient: PERF.md section 6, PR 27);
    the final norm and the tied table's probed rows."""
    second, last = weights["layers"][1], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "second.wq": second["wq"][:256],
        "second.conv1": second["conv1"],
        "second.tau": second["tau"],
        "second.wv2": second["wv2"][:512],
        "second.router.down": second["router"]["down"],
        "second.router.w3": second["router"]["w3"],
        "second.router.mix": second["router"]["mix"],
        "second.attn_scaling.out_scale": second["attn_scaling"]["out_scale"],
        "last.wo": last["wo"][:, :8],
        "last.experts.w_gate": last["w_gate"][:, :64],
        "last.experts.w_up": last["w_up"][:, :64],
        "last.experts.w_down": last["w_down"][:, :32],
        "last.mlp_scaling.res_scale": last["mlp_scaling"]["res_scale"],
        "final_norm": weights["final_norm"],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def layer_windows(config: Dict[str, Any]) -> List[Optional[int]]:
    """Every layer sees every earlier key."""
    return [None] * int(config["num_hidden_layers"])


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert's two widths, how many are held, and how much of a token's
    one pick lands on a held one if the router spreads them evenly."""
    held, every = int(config["num_experts"]), int(config["num_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["moe_intermediate_size"]), "held": held,
        "layers": int(config["num_hidden_layers"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def _layer_products(config: Dict[str, Any]) -> int:
    """Parameters of a layer that a token multiplies with, outside its
    experts: CCA's projections, the two convolutions' taps, the router's four matrices."""
    s, d, r = attention_shape(config), int(config["hidden_size"]), int(config["router_hidden_size"])
    channels = (s["heads"] + s["kv_heads"]) * s["head_dim"]
    projections = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    convolutions = channels * (int(config["cca_time0"]) + s["head_dim"] * int(config["cca_time1"]))
    return projections + convolutions + d * r + 2 * r * r + r * int(config["num_experts_published"])


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with CCA, the router, the share of ONE expert it is
    expected to find among the held ones, and the tied head."""
    e = expert_shape(config)
    expert = 3 * e["d_model"] * e["d_ff"]
    return int(e["layers"] * (_layer_products(config) + e["expected_held_picks"] * expert)) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: beside the products, the
    convolutions' and the router's biases, the state's mix and norm, the
    selection bias, two norms and eight scaling vectors a layer, a temperature
    a K/V head; the tied table once, the final norm."""
    s, e, r = attention_shape(config), expert_shape(config), int(config["router_hidden_size"])
    d = e["d_model"]
    channels = (s["heads"] + s["kv_heads"]) * s["head_dim"]
    vectors = 2 * channels + 5 * r + int(config["num_experts_published"]) + 10 * d + s["kv_heads"]
    layer = _layer_products(config) + vectors + e["held"] * 3 * d * e["d_ff"]
    return int(e["layers"] * layer) + embedding_params(config) + d
