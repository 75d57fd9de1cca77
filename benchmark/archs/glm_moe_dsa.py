"""The adapter of DeepSeek-V3's block (``archs/deepseek_mla_moe.py``: latent
attention, a dense prefix, a sigmoid router over many experts of which one chip
holds a range, a shared expert) whose attention reads only the keys a learned
indexer picks, the picks of a layer that holds an indexer shared by the layers
after it that hold none: GLM-5.2 (``model_type`` ``glm_moe_dsa``), through the
program's ``TransformerConfig`` (``indexer_types``, ``index_n_heads``,
``index_head_dim``, ``index_topk`` beside DeepSeek-V3's fields) and
``reference/glm_moe_dsa.py``.

The configuration file keeps the source's key names; ``n_routed_experts``,
``n_routed_experts_published`` and ``first_expert_held`` as DeepSeek-V3's file.
``indexer_types`` states a layer ``full`` (it holds an indexer) or ``shared``.
A serving request holds a latent row a token a layer and an index key a token a
``full`` layer, both in blocks.  Served only: the training functions refuse by
name.  Not served: the multi-token-prediction module (under ``deviations``).
"""

from __future__ import annotations

from typing import Any, Dict

from benchlib import model

dsv3 = model.beside(__file__, "archs", "deepseek_mla_moe")
reference = model.beside(__file__, "reference", "glm_moe_dsa")

embedding_params = dsv3.embedding_params
latent_shape = dsv3.latent_shape
attention_shape = dsv3.attention_shape
expert_shape = dsv3.expert_shape

#: what the program's model config has to know beside DeepSeek-V3's fields
NEEDS = dsv3.NEEDS + ("indexer_types", "index_n_heads", "index_head_dim", "index_topk", "norm_eps")
def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models.transformer import TransformerConfig

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(TransformerConfig)}
    if lacks:
        raise SpecError(f"arch glm_moe_dsa: this program's TransformerConfig lacks {', '.join(sorted(lacks))}")
    must = {
        "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu", "norm_topk_prob": True,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "rope_interleave": True,
        "indexer_rope_interleave": True, "index_topk_pattern": None,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if config["rope_parameters"]["rope_type"] != "default" or int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("the program runs plain rotary (no scaling) and one key a head from the shared latent row")
    layers, dense = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    if list(config["mlp_layer_types"]) != ["dense"] * dense + ["sparse"] * (layers - dense) or not 0 <= dense <= layers:
        raise ValueError("mlp_layer_types is first_k_dense_replace dense layers and then sparse ones")
    kinds = list(config["indexer_types"])
    if len(kinds) != layers or set(kinds) - {"full", "shared"} or kinds[0] != "full":
        raise ValueError("indexer_types states every layer full or shared, the first full")
    first, held, every = int(config["first_expert_held"]), int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    if not 0 <= first < first + held <= every:
        raise ValueError("the held experts lie inside the published ones")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["kv_cache"] != dtypes["compute"]:
        raise ValueError("the program serves float32 or bfloat16 parameters and caches rows and index keys in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def _served_only(what: str):
    raise ValueError(
        f"arch glm_moe_dsa is served only ({what}): within the guide's floors (one dense layer, a whole period of four expert "
        "layers, 8 experts, an eighth of the vocabulary) its training state is 2.67 B parameters x 16 B = 42.8 GB, over one chip's 16"
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
    return TransformerConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]), n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]), d_ff=int(config["intermediate_size"]),
        norm_eps=float(config["rms_norm_eps"]), rope_theta=float(config["rope_parameters"]["rope_theta"]),
        q_lora_rank=int(config["q_lora_rank"]), kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]), qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        indexer_types=tuple(config["indexer_types"]), index_n_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]), index_topk=int(config["index_topk"]),
        dense_prefix=int(config["first_k_dense_replace"]), moe_experts=int(config["n_routed_experts_published"]), moe_every=1,
        moe_top_k=int(config["num_experts_per_tok"]), moe_intermediate_size=int(config["moe_intermediate_size"]),
        moe_experts_held=(int(config["first_expert_held"]), int(config["n_routed_experts"])),
        moe_router="sigmoid_grouped", moe_n_group=int(config["n_group"]), moe_topk_group=int(config["topk_group"]),
        moe_routed_scaling=float(config["routed_scaling_factor"]), moe_shared_experts=int(config["n_shared_experts"]),
        max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]),
        param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call from
    the seed, in the dtype it serves them in; in that same call every layer's
    ``wq_b`` and ``wkv_b`` are multiplied by ``sqrt(heads)`` and every stack of
    held experts by ``sqrt(held)``: the program draws ``[rank, heads, dim]`` at
    a fan-in of ``rank x heads`` and ``[held, in, out]`` at ``held x in`` (flax's
    ``lecun_normal`` counts a leading axis as a receptive field), so that a
    head's and an expert's matrices come out 8 and 4 times too small (the
    file's ``assumed.initialiser`` has what that hides from the check)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        params = meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]
        times = lambda w, by: (w.astype(jnp.float32) * by).astype(w.dtype)  # noqa: E731
        for name, blk in params.items():
            if not name.startswith("block_"):
                continue
            attn = blk["attn"]
            blk = dict(blk, attn=dict(attn, **{k: times(attn[k], model_cfg.n_heads ** 0.5) for k in ("wq_b", "wkv_b")}))
            if "moe" in blk:
                blk["moe"] = dict(blk["moe"], **{k: times(blk["moe"][k], blk["moe"][k].shape[0] ** 0.5) for k in ("w_gate", "w_up", "w_down")})
            params = dict(params, **{name: blk})
        return params

    return make(jax.random.key(model.seed32(seed)))


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_INDEX = ("index_wq_b", "index_wk", "index_k_norm", "index_k_bias", "index_w")


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """DeepSeek-V3's names, and a ``full`` layer's indexer leaves under their own."""
    weights = dsv3.reference_weights(params, config)
    for i, layer in enumerate(weights["layers"]):
        attn = params[f"block_{i}"]["attn"]
        layer.update({k: attn[k] for k in _INDEX if k in attn})
    return weights


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys."""
    return {
        "eps": float(config["rms_norm_eps"]), "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "nope": int(config["qk_nope_head_dim"]), "rope_dim": int(config["qk_rope_head_dim"]), "latent": int(config["kv_lora_rank"]),
        "index_topk": int(config["index_topk"]), "top_k": int(config["num_experts_per_tok"]),
        "scaling": float(config["routed_scaling_factor"]), "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def index_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The indexer's sizes: its heads and their width (one key of ``dim`` a
    token a ``full`` layer), how many keys a query picks, how many layers hold
    an indexer and how many layers attend over picks."""
    return {
        "heads": int(config["index_n_heads"]), "dim": int(config["index_head_dim"]), "topk": int(config["index_topk"]),
        "full_layers": list(config["indexer_types"]).count("full"), "layers": int(config["num_hidden_layers"]),
    }


def indexer_params(config: Dict[str, Any]) -> int:
    """One indexer's matrices (its key's LayerNorm apart)."""
    s = index_shape(config)
    return int(config["q_lora_rank"]) * s["heads"] * s["dim"] + int(config["hidden_size"]) * (s["dim"] + s["heads"])


def matmul_params(config: Dict[str, Any]) -> int:
    """As DeepSeek-V3's, and a ``full`` layer's indexer."""
    return dsv3.matmul_params(config) + index_shape(config)["full_layers"] * indexer_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds."""
    s = index_shape(config)
    return dsv3.total_params(config) + s["full_layers"] * (indexer_params(config) + 2 * s["dim"])
