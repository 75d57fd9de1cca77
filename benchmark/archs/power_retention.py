"""The adapter of a dense decoder whose attention is gated power retention of
degree 2: Brumby-14B-Base (``model_type`` ``brumby``: Qwen3-14B-Base's block,
its softmax attention replaced), through the program's ``TransformerConfig``
(``layer_types`` of ``power_retention``, ``qk_norm``, ``retention_gate_bias``,
``param_dtype``) and ``reference/power_retention.py``.

The configuration file keeps the source's key names.  What ``config.json``
does not decide (the degree, the gate, the normaliser, the norm a head, rotary,
the gate's constant bias) is stated under ``assumed`` in the file; this module
hands both sides the same reading.  A serving request holds no keys or values
a token: ``state_shape`` counts what it holds instead.
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "power_retention")

init_params = dense.init_params
embedding_params = dense.embedding_params
attention_shape = dense.attention_shape
trial_overrides = dense.trial_overrides

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = ("layer_types", "qk_norm", "retention_gate_bias", "param_dtype", "head_dim", "norm_eps")
RETENTION = "power_retention"


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models import transformer

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if lacks or RETENTION not in getattr(transformer, "LAYER_TYPES", ()):
        raise SpecError(
            f"arch power_retention: this program's TransformerConfig lacks {', '.join(sorted(lacks)) or 'the layer type'}"
        )
    must = {
        "attention_bias": False, "hidden_act": "silu", "rope_scaling": None, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if int(config["retention_degree"]) != 2:
        raise ValueError("the program's retention is of degree 2")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["state"] != "float32":
        raise ValueError("the program serves float32 or bfloat16 parameters and keeps a float32 state")


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
        "d_ff": int(config["intermediate_size"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "layer_types": [RETENTION] * int(config["num_hidden_layers"]),
        "qk_norm": True,
        "retention_gate_bias": float(config["retention_gate_bias"]),
    }


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    h = trial_hparams(config)
    h["layer_types"] = tuple(h["layer_types"])
    return TransformerConfig(
        **h, max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]),
        param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layers.append({
            "attn_norm": b["ln1"]["scale"], "mlp_norm": b["ln2"]["scale"],
            "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"],
            **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wg", "wo")},
            **{k: b["mlp"][k]["kernel"] for k in ("w_gate", "w_up", "w_down")},
        })
    return {
        "embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"], "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys and the file's readings."""
    return {
        "eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]),
        "gate_bias": float(config["retention_gate_bias"]),
        "degree": int(config["retention_degree"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.loss_and_logits(weights, tokens, **numerics(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The leaves one training step would be compared on (no cell trains
    this configuration): the table's rows, the projections, the gate, a norm
    a head, the MLP, the head."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:256],
        "first.wg": first["wg"],
        "first.q_norm": first["q_norm"],
        "first.w_gate": first["w_gate"][:256],
        "last.wo": last["wo"][:8],
        "last.k_norm": last["k_norm"],
        "last.w_down": last["w_down"][:256],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def state_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What a request holds of one layer, whatever its length: a KV head's
    state is ``features x head_dim`` float32 values and ``features`` of the
    normaliser, where ``features = (head_dim / 2 + 1) x head_dim`` (the
    program's lane-aligned layout of the symmetric square: 8,320 at 128, of
    which 8,256 are distinct)."""
    s = attention_shape(config)
    features = (s["head_dim"] // 2 + 1) * s["head_dim"]
    return {
        **s, "features": features, "query_heads_per_kv_head": s["heads"] // s["kv_heads"],
        "bytes_per_slot": s["kv_heads"] * features * (s["head_dim"] + 1) * 4,
    }


def layer_params(config: Dict[str, Any]) -> int:
    """One layer: q, k, v, o, the gate, the two norms a head, SwiGLU, two norms."""
    d, s = int(config["hidden_size"]), attention_shape(config)
    attn = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"]) + d * s["kv_heads"] + 2 * s["head_dim"]
    return attn + 3 * d * int(config["intermediate_size"]) + 2 * d


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters in a matrix multiplication for every token: the projections,
    the gate, SwiGLU and the head (the embedding is a lookup, the norms no product)."""
    d, s = int(config["hidden_size"]), attention_shape(config)
    norms = 2 * s["head_dim"] + 2 * d
    return s["layers"] * (layer_params(config) - norms) + d * int(config["vocab_size"])


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, both tables, the final norm."""
    d = int(config["hidden_size"])
    return int(config["num_hidden_layers"]) * layer_params(config) + 2 * d * int(config["vocab_size"]) + d
