"""A test's adapter: the program's existing expert block (``models/moe.py``:
top-2 of a few experts in every block) brought to the harness by files alone,
the way a ``model_config`` PR brings an architecture.  It builds on the dense
adapter for what the two share and names its own reference.

Its configuration file adds ``num_experts``, ``moe_capacity_factor`` (set so
that no token is dropped: experts / 2), ``moe_aux_weight`` and
``norm_topk_prob`` (what the REFERENCE does with the two probabilities; the
program always renormalises, so ``false`` is a reference that is not the
program's).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "moe_top2")

check_as_run = dense.check_as_run
init_params = dense.init_params
trial_overrides = dense.trial_overrides
attention_shape = dense.attention_shape
embedding_params = dense.embedding_params

TOP_K = 2


def _moe(config: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "moe_experts": int(config["num_experts"]),
        "moe_every": 1,
        "moe_capacity_factor": float(config["moe_capacity_factor"]),
        "moe_aux_weight": float(config["moe_aux_weight"]),
    }


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    return dataclasses.replace(dense.model_config(config, max_seq_len), **_moe(config))


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    return {**dense.trial_hparams(config), **_moe(config)}


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layers.append(
            {
                "attn_norm": b["ln1"]["scale"],
                **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
                "mlp_norm": b["ln2"]["scale"],
                **{k: b["moe"][k] for k in ("router", "w_in", "w_gate", "w_out")},
            }
        )
    return {
        "embed": params["embed"]["embedding"],
        "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"],
        "layers": layers,
    }


def _numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "rope_theta": float(config["rope_theta"]),
        "eps": dense.eps_as_run(config),
        "renormalise": bool(config["norm_topk_prob"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **_numerics(config))[0]


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    """Cross-entropy + ``moe_aux_weight`` x the layers' auxiliary losses: what
    ``LMTrial.loss`` returns for a model with experts."""
    return reference.loss_and_logits(
        weights, tokens, aux_weight=float(config["moe_aux_weight"]), **_numerics(config)
    )


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The dense decoder's leaves outside the MLP, the router of the first
    and last layer and one expert's three matrices."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:256],
        "first.router": first["router"],
        "last.router": last["router"],
        "last.wo": last["wo"][:8],
        "last.expert1.w_in": last["w_in"][1],
        "last.expert1.w_gate": last["w_gate"][1],
        "last.expert1.w_out": last["w_out"][1],
        "last.mlp_norm": last["mlp_norm"],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


def _per_layer(config: Dict[str, Any], experts: int) -> int:
    s, d = attention_shape(config), int(config["hidden_size"])
    attn = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    return attn + d * int(config["num_experts"]) + experts * 3 * d * int(config["intermediate_size"])


def matmul_params(config: Dict[str, Any]) -> int:
    """A token multiplies with attention, the router, its two experts and the head."""
    return int(config["num_hidden_layers"]) * _per_layer(config, TOP_K) + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    d, layers = int(config["hidden_size"]), int(config["num_hidden_layers"])
    every = layers * _per_layer(config, int(config["num_experts"]))
    return every + 2 * embedding_params(config) + layers * 2 * d + d
