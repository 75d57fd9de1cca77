"""From a configuration file to the program's model, weights on the device.

The configuration file keeps the source's key names (``hidden_size`` ...);
this module is the one place that maps them onto the program's
``TransformerConfig`` and onto the reference's weight names.
"""

from __future__ import annotations

from typing import Any, Dict

_DTYPES = {"float32": "float32", "bfloat16": "bfloat16"}


def seed32(seed: int) -> int:
    """``--seed`` may pass 2**31; jax's keys take 31 bits."""
    return int(seed) % (2**31 - 1)


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


def transformer_config(config: Dict[str, Any], max_seq_len: int) -> Any:
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


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call
    from the seed, float32 as it trains and serves them."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    model = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        return meta.unbox(model.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]

    return make(jax.random.key(seed32(seed)))


def reference_weights(params: Dict[str, Any], n_layers: int) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views, no
    copies)."""
    layers = []
    for i in range(n_layers):
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
