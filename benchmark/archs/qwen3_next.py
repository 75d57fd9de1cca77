"""The adapter of a decoder whose layers are Gated-DeltaNet mixers with, every
``full_attention_interval``-th, a gated full-attention layer, and routed
experts beside a gated shared expert in every layer: Qwen3-Next (``model_type``
``qwen3_next``), through the program's ``TransformerConfig`` (``layer_types`` of
``linear_attention`` / ``full_attention``, the ``linear_*`` sizes, ``qk_norm``,
``attn_output_gate``, ``partial_rotary_factor``, ``moe_shared_gate``) and
``reference/qwen3_next.py``.

The configuration file keeps the source's key names.  ``num_experts`` is what
THIS chip holds (a cut, under ``reduced``), from expert ``first_expert_held``;
``num_experts_published`` is the router's width: the program and the reference
route over all of them and compute the held ones.  A serving request holds K
and V a token in blocks for the full layers and a delta-rule state and a
convolution tail in its lane for the linear layers.  Served only: the training
functions refuse by name (the file's ``deviations.training``).  Not served: the
multi-token-prediction module (under ``deviations`` in the file).
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
falcon = model.beside(__file__, "archs", "falcon_h1")
reference = model.beside(__file__, "reference", "qwen3_next")

embedding_params = dense.embedding_params
slow_heads = falcon.slow_heads  # the file's ``assumed.initialiser``: every head remembers 333 to 53,333 tokens

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "layer_types", "linear_key_heads", "linear_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv",
    "linear_chunk", "qk_norm", "attn_output_gate", "partial_rotary_factor", "moe_shared_gate", "moe_shared_experts",
    "moe_shared_intermediate_size", "moe_experts_held", "moe_top_k", "moe_router", "param_dtype", "head_dim", "norm_eps",
)
LINEAR, FULL = "linear_attention", "full_attention"


def pattern(config: Dict[str, Any]) -> List[str]:
    """The layers' types, in order: layer ``i`` is full attention where ``(i + 1) % full_attention_interval == 0``."""
    every = int(config["full_attention_interval"])
    return [FULL if (i + 1) % every == 0 else LINEAR for i in range(int(config["num_hidden_layers"]))]


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models import transformer

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if lacks or LINEAR not in getattr(transformer, "LAYER_TYPES", ()):
        raise SpecError(
            f"arch qwen3_next: this program's TransformerConfig lacks {', '.join(sorted(lacks)) or 'the layer type linear_attention'}"
        )
    must = {
        "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if int(config["full_attention_interval"]) < 2 or FULL not in pattern(config):
        raise ValueError("full_attention_interval >= 2 with at least one whole period: a period holds linear layers and one full layer")
    if int(config["linear_num_value_heads"]) % int(config["linear_num_key_heads"]):
        raise ValueError("a key head serves whole groups of value heads")
    first, held, every = int(config["first_expert_held"]), int(config["num_experts"]), int(config["num_experts_published"])
    if not 0 <= first < first + held <= every:
        raise ValueError("the held experts lie inside the published ones")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["state"] != "float32" or (
        dtypes["kv_cache"] != dtypes["compute"]
    ):
        raise ValueError("the program serves float32 or bfloat16 parameters, keeps a float32 state and caches K and V in its compute dtype")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def _served_only(what: str):
    raise ValueError(
        f"arch qwen3_next is served only ({what}): trained it fits as one of 16 chips that share each layer, where a "
        "token's picks land on a held expert 0.625 times and the step is the mixers' plain projections (ROADMAP Reach 5)"
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
        n_heads=int(config["num_attention_heads"]), n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        norm_eps=float(config["rms_norm_eps"]), layer_types=tuple(pattern(config)), rope_theta=float(config["rope_theta"]),
        partial_rotary_factor=float(config["partial_rotary_factor"]), qk_norm=True, attn_output_gate=True,
        linear_key_heads=int(config["linear_num_key_heads"]), linear_value_heads=int(config["linear_num_value_heads"]),
        linear_key_head_dim=int(config["linear_key_head_dim"]), linear_value_head_dim=int(config["linear_value_head_dim"]),
        linear_conv=int(config["linear_conv_kernel_dim"]),
        moe_experts=int(config["num_experts_published"]), moe_every=int(config["decoder_sparse_step"]),
        moe_top_k=int(config["num_experts_per_tok"]), moe_intermediate_size=int(config["moe_intermediate_size"]),
        moe_experts_held=(int(config["first_expert_held"]), int(config["num_experts"])), moe_router="softmax",
        moe_shared_experts=1, moe_shared_intermediate_size=int(config["shared_expert_intermediate_size"]), moe_shared_gate=True,
        max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]), param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call from
    the seed, in the dtype it serves them in.  In that same call each linear
    layer's ``A_log`` and ``dt_bias`` are drawn by ``slow_heads``, and each
    layer's three stacks of held experts are multiplied by ``sqrt(held)``: the
    program draws a stack ``[held, in, out]`` at a fan-in of ``held x in``
    (flax's ``lecun_normal`` counts the leading axis as a receptive field), so
    that 64 experts' matrices come out 8 times too small each and what the
    routed experts add would be ~1 / 500 of what the shared expert adds
    (Nemotron-3-Super's file's ``assumed.initialiser`` (c) has the readings)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        params = meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]
        of_heads = jax.random.fold_in(key, 0x6D4)
        whole = lambda w: (w.astype(jnp.float32) * w.shape[0] ** 0.5).astype(w.dtype)  # noqa: E731
        for name, blk in params.items():
            if "gdn" in blk:
                drawn = slow_heads(jax.random.fold_in(of_heads, int(name[6:])), model_cfg.linear_value_heads, blk["gdn"]["A_log"].dtype)
                blk = dict(blk, gdn=dict(blk["gdn"], **drawn))
            if "moe" in blk:
                blk = dict(blk, moe=dict(blk["moe"], **{n: whole(blk["moe"][n]) for n in ("w_gate", "w_up", "w_down")}))
            params = dict(params, **{name: blk})
        return params

    return make(jax.random.key(model.seed32(seed)))


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------

_MOE = ("router", "w_gate", "w_up", "w_down", "shared_w_gate", "shared_w_up", "shared_w_down", "shared_gate")


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i, kind in enumerate(pattern(config)):
        b = params[f"block_{i}"]
        if kind == LINEAR:
            mixer = {k: b["gdn"][k] for k in ("w_in", "w_ba", "conv_w", "dt_bias", "A_log", "w_out")}
            mixer["gdn_norm"] = b["gdn"]["norm"]
        else:
            mixer = {**{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")}, "q_norm": b["attn"]["q_norm"], "k_norm": b["attn"]["k_norm"]}
        layers.append({"mixer_norm": b["ln1"]["scale"], "ffn_norm": b["ln2"]["scale"], **mixer, **{k: b["moe"][k] for k in _MOE}})
    return {
        "embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"], "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys and this chip's share."""
    return {
        "eps": float(config["rms_norm_eps"]), "rope_theta": float(config["rope_theta"]),
        "rotary_dim": int(int(config["head_dim"]) * float(config["partial_rotary_factor"])),
        "heads": int(config["linear_num_value_heads"]), "key_heads": int(config["linear_num_key_heads"]),
        "key_dim": int(config["linear_key_head_dim"]), "value_dim": int(config["linear_value_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]), "top_k": int(config["num_experts_per_tok"]),
        "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each type this file runs."""
    kinds = pattern(config)
    return {kind: kinds.count(kind) for kind in (LINEAR, FULL)}


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The full layers alone: only they keep K and V a token."""
    return dict(dense.attention_shape(config), layers=layer_counts(config)[FULL])


def gdn_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What a request holds of one linear layer, whatever its length: a value
    head's state is ``key_dim x value_dim`` float32 values."""
    hk, hv, dk, dv = (int(config[k]) for k in ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    return {
        "key_heads": hk, "value_heads": hv, "key_dim": dk, "value_dim": dv, "conv": int(config["linear_conv_kernel_dim"]),
        "channels": 2 * hk * dk + hv * dv, "layers": layer_counts(config)[LINEAR], "bytes_per_slot": hv * dk * dv * 4,
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert as it is held (three matrices of ``d_model x d_ff``), how many
    are held and in how many layers, and how many of a token's picks land on a
    held one if the router spreads them evenly."""
    held, every = int(config["num_experts"]), int(config["num_experts_published"])
    return {
        "d_model": int(config["hidden_size"]), "d_ff": int(config["moe_intermediate_size"]), "matrices": 3,
        "held": held, "layers": int(config["num_hidden_layers"]), "shared_d_ff": int(config["shared_expert_intermediate_size"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A linear layer's mixer: the two in-projections (q, k, v, z; b, a), the
    convolution, ``dt_bias`` / ``A_log``, the gated norm, the out-projection."""
    d, s = int(config["hidden_size"]), gdn_shape(config)
    width = s["value_heads"] * s["value_dim"]
    return {
        "w_in": d * (s["channels"] + width), "w_ba": d * 2 * s["value_heads"], "conv": s["conv"] * s["channels"],
        "scalars": 2 * s["value_heads"], "norm": s["value_dim"], "w_out": width * d,
    }


def attention_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A full layer's mixer: ``wq`` (query AND output gate), ``wk``, ``wv``, ``wo``, the two head norms."""
    d, s = int(config["hidden_size"]), attention_shape(config)
    return {"matrices": d * s["head_dim"] * (3 * s["heads"] + 2 * s["kv_heads"]), "norms": 2 * s["head_dim"]}


def expert_layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """What every layer holds beside its mixer: the router, the shared expert
    and its gate, ONE routed expert (``expert``), the layer's two norms."""
    d, e = int(config["hidden_size"]), expert_shape(config)
    return {
        "router": d * int(config["num_experts_published"]), "shared": 3 * d * e["shared_d_ff"], "shared_gate": d,
        "expert": 3 * d * e["d_ff"], "norms": 2 * d,
    }


def layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Every leaf of one layer of each type as this chip holds it."""
    e, held = expert_layer_params(config), int(config["num_experts"])
    beside = e["router"] + e["shared"] + e["shared_gate"] + e["norms"] + held * e["expert"]
    return {LINEAR: sum(mixer_params(config).values()) + beside, FULL: sum(attention_params(config).values()) + beside}


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters in a matrix multiplication for every token: a linear layer's
    three projections, a full layer's four, every layer's router, shared expert
    (its gate a vector product) and the token's expected held picks, and the
    head (the embedding is a lookup; norms, the convolution and the rule's
    scalars no product with a matrix)."""
    n, m, e = layer_counts(config), mixer_params(config), expert_layer_params(config)
    experts = e["router"] + e["shared"] + e["shared_gate"] + expert_shape(config)["expected_held_picks"] * e["expert"]
    mixers = n[LINEAR] * (m["w_in"] + m["w_ba"] + m["w_out"]) + n[FULL] * attention_params(config)["matrices"]
    return mixers + (n[LINEAR] + n[FULL]) * experts + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, both tables, the final norm."""
    n, per = layer_counts(config), layer_params(config)
    return sum(n[kind] * per[kind] for kind in (LINEAR, FULL)) + 2 * embedding_params(config) + int(config["hidden_size"])
