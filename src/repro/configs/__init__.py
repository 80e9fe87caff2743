"""Architecture registry: ``--arch <id>`` lookup for every assigned config.

``get_config(arch_id)`` returns the full paper-exact ModelConfig;
``get_smoke_config(arch_id)`` returns the reduced same-family variant used
by the CPU smoke tests (2 layers, d_model<=512, <=4 experts).
``from_published(config)`` translates a published ``config.json`` (as a
benchmark configuration file states it) into a ModelConfig.
"""

from __future__ import annotations

import importlib

from .base import INPUT_SHAPES, InputShape, ModelConfig, shape_applicable

_MODULES = {
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "minitron-4b": "minitron_4b",
    "yi-6b": "yi_6b",
    "mixtral-8x22b": "mixtral_8x22b",
    "h2o-danube-3-4b": "h2o_danube_3_4b",
    "starcoder2-3b": "starcoder2_3b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "mamba2-1.3b": "mamba2_1_3b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCH_IDS = tuple(_MODULES)


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(
            f"unknown arch {arch_id!r}; known: {', '.join(ARCH_IDS)}")
    return importlib.import_module(f".{_MODULES[arch_id]}", __package__)


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).smoke_config()


def all_configs():
    return {a: get_config(a) for a in ARCH_IDS}


_DENSE_ACTS = {"gelu_pytorch_tanh": "gelu", "relu": "relu"}


def from_published(c: dict) -> ModelConfig:
    """The ModelConfig of a published configuration as run: a dense
    stack of the paged path, or a ``deepseek_v2`` stack.  What the
    program cannot compute raises ``ValueError``, never runs as
    something else."""
    if c.get("model_type") == "deepseek_v2":
        return _deepseek_v2(c)
    if (c["hidden_act"] not in _DENSE_ACTS or c.get("mlp_gated")
            or c.get("use_bias") or c.get("norm_type") != "rms_norm_1p"
            or c.get("partial_rotary_factor", 1.0) != 1.0
            or c.get("sliding_window")):
        raise ValueError(f"{c['name']}: the program's paged path runs "
                         "ungated, biasless, full-attention stacks with "
                         "(1 + w) RMSNorm and full rotary")
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], mlp_act=_DENSE_ACTS[c["hidden_act"]],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c.get("norm_epsilon", c.get("norm_eps"))),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        source=c["source"])


def _deepseek_v2(c: dict) -> ModelConfig:
    refuse = {
        "q_lora_rank": c.get("q_lora_rank") is not None,
        "topk_method": c.get("topk_method", "greedy") != "greedy",
        "n_group": (c.get("n_group") or 1) > 1,
        "scoring_func": c.get("scoring_func", "softmax") != "softmax",
        "hidden_act": c.get("hidden_act") != "silu",
        "attention_bias": bool(c.get("attention_bias")),
        "moe_layer_freq": c.get("moe_layer_freq", 1) != 1,
        "norm_type": c.get("norm_type") != "rms_norm_1p",
        "routed_scaling_factor": c.get("routed_scaling_factor", 1) != 1,
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise ValueError(f"{c['name']}: the program does not compute "
                         f"{', '.join(f'{k}={c.get(k)!r}' for k in bad)}")
    rs = c.get("rope_scaling") or {}
    if rs and (rs.get("type") != "yarn"
               or (rs.get("beta_fast", 32), rs.get("beta_slow", 1)) != (32, 1)):
        raise ValueError(f"{c['name']}: the program does not compute "
                         f"rope_scaling={rs!r} (yarn with beta_fast 32 and "
                         "beta_slow 1 only)")
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        rope_factor=float(rs.get("factor", 0.0)),
        rope_original_max_pos=int(rs.get("original_max_position_embeddings",
                                         0)),
        rope_mscale=float(rs.get("mscale", 1.0)),
        rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        d_ff=c["intermediate_size"], mlp_act="swiglu",
        num_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c.get("n_shared_experts") or 0,
        num_dense_layers=c.get("first_k_dense_replace", 0),
        norm_topk_prob=bool(c.get("norm_topk_prob")),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        source=c["source"])


__all__ = [
    "ARCH_IDS", "INPUT_SHAPES", "InputShape", "ModelConfig",
    "all_configs", "from_published", "get_config", "get_smoke_config",
    "shape_applicable",
]
