"""The adapter of the dense decoder (``models/transformer.py`` with no
experts): Mistral, InternLM2 and any file that names no ``arch``.

The configuration file keeps the source's key names (``hidden_size`` ...);
this module is the one place that maps them onto the program's
``TransformerConfig`` and ``LMTrial`` and onto the weight names of
``reference/dense_decoder.py``, and the only importer of that reference.
``benchlib/model.py`` lists what an adapter defines.
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import model

reference = model.beside(__file__, "reference", "dense_decoder")

_DTYPES = {"float32": "float32", "bfloat16": "bfloat16"}


def eps_as_run(config: Dict[str, Any]) -> float:
    """The RMSNorm epsilon the program runs: the source's, unless the file
    states a deviation."""
    return float(config.get("deviations", {}).get("rms_norm_eps", {}).get("as_run", config["rms_norm_eps"]))


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a configuration file that states what the program cannot run
    as stated, instead of running something else under its name."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if int(config.get("head_dim", d // h)) != d // h:
        raise ValueError("the program derives head_dim as hidden_size / heads")
    if eps_as_run(config) != 1e-6:
        raise ValueError("the program fixes rms_norm_eps at 1e-6: state that, under `deviations`")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the program's head is untied")
    if config["dtypes"]["compute"] not in _DTYPES or config["dtypes"]["serve_params"] != "float32":
        raise ValueError("the program serves float32 parameters; compute is float32 or bfloat16")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["dtypes"]["compute"]),
    )


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of ``LMTrial``'s hparams."""
    return {
        "vocab_size": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_ff": int(config["intermediate_size"]),
    }


def trial_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``LMTrial`` takes from no hparam: replaced on its model config,
    as a user would in a subclass."""
    return {"rope_theta": float(config["rope_theta"])}


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call
    from the seed, float32 as it trains and serves them."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        return meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]

    return make(jax.random.key(model.seed32(seed)))


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views, no
    copies)."""
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        b = params[f"block_{i}"]
        layers.append(
            {
                "attn_norm": b["ln1"]["scale"],
                "wq": b["attn"]["wq"]["kernel"],
                "wk": b["attn"]["wk"]["kernel"],
                "wv": b["attn"]["wv"]["kernel"],
                "wo": b["attn"]["wo"]["kernel"],
                "mlp_norm": b["ln2"]["scale"],
                "w_gate": b["mlp"]["w_gate"]["kernel"],
                "w_up": b["mlp"]["w_up"]["kernel"],
                "w_down": b["mlp"]["w_down"]["kernel"],
            }
        )
    return {
        "embed": params["embed"]["embedding"],
        "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"],
        "layers": layers,
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, rope_theta=float(config["rope_theta"]), eps=eps_as_run(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    """The loss the program trains on (mean cross-entropy, no auxiliary
    term) and the logits it was taken from."""
    return reference.loss_and_logits(weights, tokens, rope_theta=float(config["rope_theta"]), eps=eps_as_run(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """A few leaves (or their first rows) of a tree under the reference's
    names: what one step's update is compared on.  The embedding's rows are
    given: some that the batch holds and some that it does not."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:256],
        "first.w_gate": first["w_gate"][:256],
        "last.wo": last["wo"][:8],
        "last.w_down": last["w_down"][:256],
        "last.mlp_norm": last["mlp_norm"],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for benchlib/costs.py
# ---------------------------------------------------------------------------


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "heads": h,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim", d // h)),
        "layers": int(config["num_hidden_layers"]),
    }


def embedding_params(config: Dict[str, Any]) -> int:
    """The embedding is a lookup of one row a token."""
    return int(config["hidden_size"]) * int(config["vocab_size"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    q, k, v, o, the gated MLP's three, and the output head (the embedding is
    a lookup)."""
    s, d = attention_shape(config), int(config["hidden_size"])
    attn = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    return s["layers"] * (attn + 3 * d * int(config["intermediate_size"])) + d * int(config["vocab_size"])


def total_params(config: Dict[str, Any]) -> int:
    d = int(config["hidden_size"])
    norms = int(config["num_hidden_layers"]) * 2 * d + d
    return matmul_params(config) + embedding_params(config) + norms
