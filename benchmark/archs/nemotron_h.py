"""The adapter of a decoder whose layers are ONE mixer each, a Mamba-2 mixer,
attention or an expert layer, by the letters of ``hybrid_override_pattern``
(``M``, ``*``, ``E``), and whose experts are two-matrix squared-ReLU ones that
work in a latent between two projections all of them share: Nemotron-3-Super
(``model_type`` ``nemotron_h``), through the program's ``TransformerConfig``
(``mixer_block``, ``layer_types`` of ``mamba2`` / ``full_attention`` /
``experts``, the ``ssm_*`` sizes, ``moe_expert_act``, ``moe_latent_size``,
``moe_shared_intermediate_size``) and ``reference/nemotron_h.py``.

The configuration file keeps the source's key names.  ``n_routed_experts`` is
what THIS chip holds (a cut, under ``reduced``), from expert
``first_expert_held``; ``n_routed_experts_published`` is the router's width:
the program and the reference route over all of them and compute the held
ones.  A serving request holds K and V a token in blocks for the ``*`` layers,
a state and a convolution tail in its lane for the ``M`` layers, and nothing
for the ``E`` layers.  Served only: the training functions refuse by name (the
guide's floors are 19.4 GB of training state).  Not served: the
multi-token-prediction module (``num_nextn_predict_layers``; under
``deviations`` in the file).
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
falcon = model.beside(__file__, "archs", "falcon_h1")
reference = model.beside(__file__, "reference", "nemotron_h")

embedding_params = dense.embedding_params
attention_shape_of = dense.attention_shape
slow_heads = falcon.slow_heads  # the file's ``assumed.initialiser``: every head remembers 333 to 53,333 tokens

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "mixer_block", "layer_types", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv", "ssm_chunk",
    "moe_expert_act", "moe_latent_size", "moe_shared_intermediate_size", "moe_router", "moe_routed_scaling",
    "moe_shared_experts", "moe_experts_held", "moe_top_k", "rope_parameters", "param_dtype", "head_dim", "norm_eps",
)
#: a letter of ``hybrid_override_pattern`` -> the program's layer type under ``mixer_block``
LETTERS = {"M": "mamba2", "*": "full_attention", "E": "experts"}


def pattern(config: Dict[str, Any]) -> List[str]:
    """The layers' letters, in order."""
    return list(config["hybrid_override_pattern"])


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models import transformer

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if lacks or not set(LETTERS.values()) <= set(getattr(transformer, "LAYER_TYPES", ())):
        raise SpecError(
            f"arch nemotron_h: this program's TransformerConfig lacks {', '.join(sorted(lacks)) or 'the layer types mamba2 and experts'}"
        )
    must = {
        "attention_bias": False, "mamba_proj_bias": False, "use_conv_bias": True, "mlp_bias": False, "use_bias": False,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "tie_word_embeddings": False, "sliding_window": None, "n_shared_experts": 1, "moe_shared_expert_overlap": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    letters = pattern(config)
    if set(letters) - set(LETTERS) or len(letters) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern is one of M, * and E for each of num_hidden_layers layers (no dense `-` layer is run)")
    if int(config["mamba_num_heads"]) * int(config["mamba_head_dim"]) != int(config["expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba_num_heads heads of mamba_head_dim are expand x hidden_size channels")
    if float(config["layer_norm_epsilon"]) != float(config["norm_eps"]):
        raise ValueError("layer_norm_epsilon and norm_eps say the same")
    first, held, every = int(config["first_expert_held"]), int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
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
        f"arch nemotron_h is served only ({what}): within the guide's floors (a whole period of 11 layers, 8 experts, an "
        "eighth of the vocabulary) its training state is 1,210,931,584 parameters x 16 B = 19.4 GB, over one chip's 16"
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
        norm_eps=float(config["norm_eps"]), mixer_block=True, layer_types=tuple(LETTERS[c] for c in pattern(config)),
        # the published modelling code turns no q and no k: rope_theta and partial_rotary_factor are read by nothing
        rope_parameters={"full_attention": {"rope_type": "none"}},
        ssm_heads=int(config["mamba_num_heads"]), ssm_head_dim=int(config["mamba_head_dim"]), ssm_state=int(config["ssm_state_size"]),
        ssm_groups=int(config["n_groups"]), ssm_conv=int(config["conv_kernel"]), ssm_chunk=int(config["chunk_size"]),
        moe_experts=int(config["n_routed_experts_published"]), moe_top_k=int(config["num_experts_per_tok"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        moe_experts_held=(int(config["first_expert_held"]), int(config["n_routed_experts"])),
        moe_router="sigmoid_grouped", moe_n_group=int(config["n_group"]), moe_topk_group=int(config["topk_group"]),
        moe_routed_scaling=float(config["routed_scaling_factor"]), moe_shared_experts=int(config["n_shared_experts"]),
        moe_shared_intermediate_size=int(config["moe_shared_expert_intermediate_size"]), moe_expert_act="relu2",
        moe_latent_size=int(config["moe_latent_size"]),
        max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]), param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call from
    the seed, in the dtype it serves them in.  In that same call each ``M``
    layer's ``A_log`` and ``dt_bias`` are drawn by ``slow_heads``, and each
    ``E`` layer's two stacks of held experts are multiplied by ``sqrt(held)``:
    the program draws a stack ``[held, in, out]`` at a fan-in of ``held x in``
    (flax's ``lecun_normal`` counts the leading axis as a receptive field), so
    that 128 experts' matrices come out 11.3 times too small each and what the
    routed experts add is ~1 / 1,400 of what the shared expert adds: no check
    could then see the latent, the scaling or the weights' normalisation left
    out (the file's ``assumed.initialiser`` (c) has the readings)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)

    @jax.jit
    def make(key):
        params = meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]
        of_heads = jax.random.fold_in(key, 0x55D)
        for name, blk in params.items():
            if "ssm" in blk:
                drawn = slow_heads(jax.random.fold_in(of_heads, int(name[6:])), model_cfg.ssm_heads, blk["ssm"]["A_log"].dtype)
                params = dict(params, **{name: dict(blk, ssm=dict(blk["ssm"], **drawn))})
            if "moe" in blk:
                whole = lambda w: (w.astype(jnp.float32) * w.shape[0] ** 0.5).astype(w.dtype)  # noqa: E731
                params = dict(params, **{name: dict(blk, moe=dict(blk["moe"], w_up=whole(blk["moe"]["w_up"]), w_down=whole(blk["moe"]["w_down"])))})
        return params

    return make(jax.random.key(model.seed32(seed)))


# ---------------------------------------------------------------------------
# onto the reference
# ---------------------------------------------------------------------------


def reference_weights(params: Dict[str, Any], config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names (views: the
    leaves stay in the dtype the program serves them in)."""
    layers = []
    for i, letter in enumerate(pattern(config)):
        b = params[f"block_{i}"]
        if letter == "M":
            mixer = {k: b["ssm"][k] for k in ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "w_out")}
            mixer["ssm_norm"] = b["ssm"]["norm"]
        elif letter == "*":
            mixer = {k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")}
        else:
            mixer = {k: b["moe"][k] for k in (
                "router", "router_bias", "w_latent_in", "w_latent_out", "w_up", "w_down", "shared_w_up", "shared_w_down")}
        layers.append({"norm": b["ln1"]["scale"], **mixer})
    return {
        "embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
        "final_norm": params["ln_f"]["scale"], "layers": layers,
    }


def numerics(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference is told of the configuration: the published keys and this chip's share."""
    return {
        "eps": float(config["norm_eps"]),
        "heads": int(config["mamba_num_heads"]), "head_dim": int(config["mamba_head_dim"]),
        "d_state": int(config["ssm_state_size"]), "groups": int(config["n_groups"]), "conv": int(config["conv_kernel"]),
        "top_k": int(config["num_experts_per_tok"]), "scaling": float(config["routed_scaling_factor"]),
        "first_expert": int(config["first_expert_held"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each letter this file runs."""
    letters = pattern(config)
    return {letter: letters.count(letter) for letter in LETTERS}


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The ``*`` layers alone: only they keep K and V a token."""
    return dict(attention_shape_of(config), layers=layer_counts(config)["*"])


def ssm_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What a request holds of one ``M`` layer, whatever its length: a head's
    state is ``head_dim x d_state`` float32 values."""
    h, p, n, g = (int(config[k]) for k in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    return {
        "heads": h, "head_dim": p, "d_state": n, "groups": g, "conv": int(config["conv_kernel"]),
        "channels": h * p + 2 * g * n, "layers": layer_counts(config)["M"], "bytes_per_slot": h * p * n * 4,
    }


def expert_shape(config: Dict[str, Any]) -> Dict[str, float]:
    """An expert as it is held: TWO matrices of ``d_model x d_ff`` where
    ``d_model`` is the LATENT width the experts work in (``model_width`` the
    stream's); how many are held and in how many layers, and how many of a
    token's picks land on a held one if the router spreads them evenly."""
    held, every = int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    return {
        "d_model": int(config["moe_latent_size"]), "d_ff": int(config["moe_intermediate_size"]), "matrices": 2,
        "model_width": int(config["hidden_size"]), "held": held, "layers": layer_counts(config)["E"],
        "shared": int(config["n_shared_experts"]), "shared_d_ff": int(config["moe_shared_expert_intermediate_size"]),
        "expected_held_picks": int(config["num_experts_per_tok"]) * held / every,
    }


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """An ``M`` layer's leaves: the in-projection (z, x, B, C, dt), the
    convolution and its bias, ``dt_bias`` / ``A_log`` / ``D``, the gated norm,
    the out-projection."""
    d, s = int(config["hidden_size"]), ssm_shape(config)
    width = s["heads"] * s["head_dim"]
    return {
        "w_in": d * (width + s["channels"] + s["heads"]), "conv": (s["conv"] + 1) * s["channels"],
        "scalars": 3 * s["heads"], "norm": width, "w_out": width * d,
    }


def attention_params(config: Dict[str, Any]) -> int:
    """A ``*`` layer's four matrices."""
    d, s = int(config["hidden_size"]), attention_shape(config)
    return d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])


def expert_layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """An ``E`` layer's leaves: the router and its bias, the two latent
    projections, the shared expert, ONE routed expert (``expert``: two
    matrices at the latent width)."""
    d, e = int(config["hidden_size"]), expert_shape(config)
    return {
        "router": d * int(config["n_routed_experts_published"]), "router_bias": int(config["n_routed_experts_published"]),
        "latent": 2 * d * e["d_model"], "shared": 2 * d * e["shared_d_ff"], "expert": 2 * e["d_model"] * e["d_ff"],
    }


def layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Every leaf of one layer of each letter as this chip holds it, its norm among them."""
    d, e, held = int(config["hidden_size"]), expert_layer_params(config), int(config["n_routed_experts"])
    return {
        "M": sum(mixer_params(config).values()) + d, "*": attention_params(config) + d,
        "E": e["router"] + e["router_bias"] + e["latent"] + e["shared"] + held * e["expert"] + d,
    }


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters in a matrix multiplication for every token: an ``M`` layer's
    two projections, a ``*`` layer's four, an ``E`` layer's router, latent
    projections, shared expert and the token's expected held picks, and the
    head (the embedding is a lookup; norms, the convolution and the scan's
    scalars no product with a matrix)."""
    n, m, e = layer_counts(config), mixer_params(config), expert_layer_params(config)
    experts = e["router"] + e["latent"] + e["shared"] + expert_shape(config)["expected_held_picks"] * e["expert"]
    return n["M"] * (m["w_in"] + m["w_out"]) + n["*"] * attention_params(config) + n["E"] * experts + embedding_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, both tables, the final norm."""
    n, per = layer_counts(config), layer_params(config)
    return sum(n[letter] * per[letter] for letter in LETTERS) + 2 * embedding_params(config) + int(config["hidden_size"])
