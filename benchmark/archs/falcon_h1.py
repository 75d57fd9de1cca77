"""The adapter of a dense decoder whose block runs attention heads and
Mamba-2 heads side by side under one norm: Falcon-H1 (``model_type``
``falcon_h1``), through the program's ``TransformerConfig`` (``layer_types`` of
``attention_mamba2``, the ``ssm_*`` sizes, muP's scalars, ``param_dtype``) and
``reference/falcon_h1.py``.

The configuration file keeps the source's key names.  What ``config.json``
does not decide (how the gated norm groups its channels, the dtype of the
state, the initialisers) is stated under ``assumed`` in the file; this module
hands both sides the same reading.  A serving request holds K and V a token in
blocks AND a state and a convolution tail in its lane: ``ssm_shape`` counts
the second.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchlib import model

dense = model.beside(__file__, "archs", "dense_decoder")
reference = model.beside(__file__, "reference", "falcon_h1")

embedding_params = dense.embedding_params
attention_shape = dense.attention_shape
trial_overrides = dense.trial_overrides

#: what the program's model config has to know before this adapter can hand it a file
NEEDS = (
    "layer_types", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv", "ssm_chunk", "embedding_multiplier",
    "key_multiplier", "attention_in_multiplier", "attention_out_multiplier", "ssm_in_multiplier", "ssm_multipliers",
    "ssm_out_multiplier", "mlp_multipliers", "logit_scale", "param_dtype", "head_dim", "norm_eps",
)
HYBRID = "attention_mamba2"


def check_as_run(config: Dict[str, Any]) -> None:
    """Refuse a file that states what the program does not run, and a
    program that does not know what the file states."""
    import dataclasses

    from benchlib.spec import SpecError
    from determined_tpu.models import transformer

    lacks = set(NEEDS) - {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if lacks or HYBRID not in getattr(transformer, "LAYER_TYPES", ()):
        raise SpecError(
            f"arch falcon_h1: this program's TransformerConfig lacks {', '.join(sorted(lacks)) or 'the layer type'}"
        )
    must = {
        "attention_bias": False, "hidden_act": "silu", "rope_scaling": None, "tie_word_embeddings": False,
        "attn_layer_indices": None, "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_rms_norm": True,
        "mamba_norm_before_gate": False, "mlp_bias": False, "projectors_bias": False,
    }
    for key, value in must.items():
        if config[key] != value:
            raise ValueError(f"the program runs {key} = {value!r}; the file states {config[key]!r}")
    if int(config["mamba_d_ssm"]) != int(config["mamba_n_heads"]) * int(config["mamba_d_head"]):
        raise ValueError("mamba_d_ssm is mamba_n_heads heads of mamba_d_head")
    dtypes = config["dtypes"]
    if any(dtypes[k] not in ("float32", "bfloat16") for k in ("serve_params", "compute")) or dtypes["state"] != "float32":
        raise ValueError("the program serves float32 or bfloat16 parameters and keeps a float32 state")


# ---------------------------------------------------------------------------
# onto the program
# ---------------------------------------------------------------------------


def trial_hparams(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's part of the program's model config, under its own names (no
    cell trains this configuration, and ``LMTrial`` reads none of the ``ssm_*``)."""
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
        "layer_types": [HYBRID] * int(config["num_hidden_layers"]),
        "ssm_heads": int(config["mamba_n_heads"]),
        "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]),
        "ssm_groups": int(config["mamba_n_groups"]),
        "ssm_conv": int(config["mamba_d_conv"]),
        "ssm_chunk": int(config["mamba_chunk_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "key_multiplier": float(config["key_multiplier"]),
        "attention_in_multiplier": float(config["attention_in_multiplier"]),
        "attention_out_multiplier": float(config["attention_out_multiplier"]),
        "ssm_in_multiplier": float(config["ssm_in_multiplier"]),
        "ssm_multipliers": [float(m) for m in config["ssm_multipliers"]],
        "ssm_out_multiplier": float(config["ssm_out_multiplier"]),
        "mlp_multipliers": [float(m) for m in config["mlp_multipliers"]],
        "logit_scale": float(config["lm_head_multiplier"]),
    }


def model_config(config: Dict[str, Any], max_seq_len: int) -> Any:
    import jax.numpy as jnp

    from determined_tpu.models.transformer import TransformerConfig

    check_as_run(config)
    h = trial_hparams(config)
    for key in ("layer_types", "ssm_multipliers", "mlp_multipliers"):
        h[key] = tuple(h[key])
    return TransformerConfig(
        **h, max_seq_len=int(max_seq_len), dtype=jnp.dtype(config["dtypes"]["compute"]),
        param_dtype=jnp.dtype(config["dtypes"]["serve_params"]),
    )


def init_scales(cfg: Any) -> Dict[str, Any]:
    """What ``init_params`` multiplies a drawn leaf by: one over the scalar the
    forward multiplies its product by, so that a fresh block's activations are
    those of the same block without muP (the file's ``assumed.initialiser``:
    seeded weights are not trained ones, and behind scalars of 0.01 to 0.1 no
    check could see a branch left out).  ``w_in`` a segment of its columns."""
    import numpy as np

    state = cfg.ssm_groups * cfg.ssm_state
    sizes = (cfg.ssm_width, cfg.ssm_width, state, state, cfg.ssm_heads)
    through = cfg.attention_in_multiplier
    return {
        "embed": 1.0 / cfg.embedding_multiplier, "lm_head": 1.0 / cfg.logit_scale,
        "wq": 1.0 / through, "wv": 1.0 / through, "wk": 1.0 / (through * cfg.key_multiplier),
        "wo": 1.0 / cfg.attention_out_multiplier,
        "w_in": np.repeat(1.0 / (cfg.ssm_in_multiplier * np.asarray(cfg.ssm_multipliers, np.float64)), sizes),
        "w_out": 1.0 / cfg.ssm_out_multiplier,
        "w_gate": 1.0 / cfg.mlp_multipliers[0], "w_down": 1.0 / cfg.mlp_multipliers[1],
    }


#: the file's ``assumed.initialiser`` (a): ``A`` is Mamba-2's U(1, 16) over this, the step log-uniform in this range
SLOW_A_OVER, SLOW_STEP = 16.0, (3e-4, 3e-3)


def slow_heads(key: Any, heads: int, dtype: Any) -> Dict[str, Any]:
    """``A_log`` and ``dt_bias`` of one layer, ``dtype`` [heads]: every head
    remembers ``1 / (A dt)`` = 333 to 53,333 tokens, so that what a state held
    in too few bits loses a token adds up over a sequence of the check's length
    (Mamba-2's own draw, a median of ~9 tokens, forgets it at once: the file's
    ``assumed.initialiser`` has both readings)."""
    import jax
    import jax.numpy as jnp

    of_a, of_step = jax.random.split(key)
    low, high = (math.log(v) for v in SLOW_STEP)
    step = jnp.exp(jax.random.uniform(of_step, (heads,), jnp.float32, low, high))
    return {
        "A_log": jnp.log(jax.random.uniform(of_a, (heads,), jnp.float32, 1.0, 16.0) / SLOW_A_OVER).astype(dtype),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),  # softplus's inverse
    }


def init_params(model_cfg: Any, seed: int) -> Dict[str, Any]:
    """The program's own initialiser, run on the device in one jitted call from
    the seed, in the dtype it serves them in; each leaf behind one of muP's
    scalars is then multiplied by its ``init_scales`` and each layer's ``A_log``
    and ``dt_bias`` drawn by ``slow_heads``, in that same call."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from determined_tpu.models.transformer import TransformerLM

    lm = TransformerLM(model_cfg)
    scales = init_scales(model_cfg)

    @jax.jit
    def make(key):
        params = meta.unbox(lm.init(key, jnp.zeros((1, 8), jnp.int32)))["params"]
        of_heads = jax.random.fold_in(key, 0x55D)
        times = lambda leaf, by: (leaf.astype(jnp.float32) * jnp.asarray(by, jnp.float32)).astype(leaf.dtype)  # noqa: E731
        kernel = lambda sub, name: {"kernel": times(sub[name]["kernel"], scales[name])}  # noqa: E731
        out = dict(params, embed={"embedding": times(params["embed"]["embedding"], scales["embed"])}, lm_head=kernel(params, "lm_head"))
        for name in (n for n in params if n.startswith("block_")):
            blk = params[name]
            out[name] = dict(
                blk,
                attn={k: kernel(blk["attn"], k) for k in blk["attn"]},
                ssm=dict(
                    blk["ssm"], w_in=times(blk["ssm"]["w_in"], scales["w_in"]), w_out=times(blk["ssm"]["w_out"], scales["w_out"]),
                    **slow_heads(jax.random.fold_in(of_heads, int(name[6:])), model_cfg.ssm_heads, blk["ssm"]["A_log"].dtype),
                ),
                mlp=dict(blk["mlp"], w_gate=kernel(blk["mlp"], "w_gate"), w_down=kernel(blk["mlp"], "w_down")),
            )
        return out

    return make(jax.random.key(model.seed32(seed)))


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
            **{k: b["attn"][k]["kernel"] for k in ("wq", "wk", "wv", "wo")},
            **{k: b["ssm"][k] for k in ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "w_out")},
            "ssm_norm": b["ssm"]["norm"],
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
        "heads": int(config["mamba_n_heads"]), "head_dim": int(config["mamba_d_head"]),
        "d_state": int(config["mamba_d_state"]), "groups": int(config["mamba_n_groups"]), "conv": int(config["mamba_d_conv"]),
        **{k: float(config[k]) for k in (
            "embedding_multiplier", "key_multiplier", "attention_in_multiplier", "attention_out_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier", "lm_head_multiplier",
        )},
        "ssm_multipliers": tuple(float(m) for m in config["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(m) for m in config["mlp_multipliers"]),
    }


def reference_forward(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.forward(weights, tokens, **numerics(config))


def reference_loss_and_logits(weights: Dict[str, Any], tokens: Any, config: Dict[str, Any]) -> Any:
    return reference.loss_and_logits(weights, tokens, **numerics(config))


def probe(weights: Dict[str, Any], embed_rows: Any) -> Dict[str, Any]:
    """The leaves one training step would be compared on (no cell trains
    this configuration): the table's rows, both mixers' projections, the
    scan's own leaves, the MLP, the head."""
    first, last = weights["layers"][0], weights["layers"][-1]
    return {
        "embed": weights["embed"][embed_rows],
        "first.wq": first["wq"][:256],
        "first.w_in": first["w_in"][:256],
        "first.conv_w": first["conv_w"],
        "first.A_log": first["A_log"],
        "first.w_gate": first["w_gate"][:256],
        "last.wo": last["wo"][:4],
        "last.dt_bias": last["dt_bias"],
        "last.ssm_norm": last["ssm_norm"],
        "last.w_out": last["w_out"][:256],
        "last.w_down": last["w_down"][:256],
        "final_norm": weights["final_norm"],
        "head": weights["head"][:256],
    }


# ---------------------------------------------------------------------------
# counts, for the cost functions
# ---------------------------------------------------------------------------


def ssm_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What a request holds of one layer's Mamba-2 mixer, whatever its length:
    a head's state is ``head_dim x d_state`` float32 values."""
    h, p, n, g = (int(config[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    return {
        "heads": h, "head_dim": p, "d_state": n, "groups": g, "conv": int(config["mamba_d_conv"]),
        "channels": h * p + 2 * g * n, "layers": int(config["num_hidden_layers"]), "bytes_per_slot": h * p * n * 4,
    }


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """The Mamba-2 mixer's leaves: the in-projection (z, x, B, C, dt), the
    convolution and its bias, ``dt_bias`` / ``A_log`` / ``D``, the gated norm, the
    out-projection."""
    d, s = int(config["hidden_size"]), ssm_shape(config)
    width = s["heads"] * s["head_dim"]
    return {
        "w_in": d * (width + s["channels"] + s["heads"]), "conv": (s["conv"] + 1) * s["channels"],
        "scalars": 3 * s["heads"], "norm": width, "w_out": width * d,
    }


def layer_params(config: Dict[str, Any]) -> int:
    """One layer: q, k, v, o; the Mamba-2 mixer; SwiGLU; two norms."""
    d, s = int(config["hidden_size"]), attention_shape(config)
    attn = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    return attn + sum(mixer_params(config).values()) + 3 * d * int(config["intermediate_size"]) + 2 * d


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters in a matrix multiplication for every token: both mixers'
    projections, SwiGLU and the head (the embedding is a lookup; the norms, the
    convolution and the scan's scalars no product with a matrix)."""
    d, s, m = int(config["hidden_size"]), attention_shape(config), mixer_params(config)
    attn = d * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    return s["layers"] * (attn + m["w_in"] + m["w_out"] + 3 * d * int(config["intermediate_size"])) + d * int(config["vocab_size"])


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, both tables, the final norm."""
    d = int(config["hidden_size"])
    return int(config["num_hidden_layers"]) * layer_params(config) + 2 * d * int(config["vocab_size"]) + d
