"""A LongCat-Flash-shaped model at a tiny size for the CPU tests (two double
layers: latent attention with a query low-rank and the two latent scales,
a dense SwiGLU in each sub-layer, the shortcut MoE of 8 real and 4 identity
experts under a biased router, top-3 times a scale, not renormalised),
float32, seeded weights, and the plain reference the benchmark keeps
(``benchmarks/reference/longcat_flash.py``, loaded from its file: it imports
nothing but jax and numpy and shares no code with the package)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from zookeeper_tpu import configure
from zookeeper_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("longcat_flash_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


VOCAB, POSITIONS = 512, 96
REAL, ZERO = 8, 4

#: The reference's view of the model: the public config's keys, and the
#: router's real experts and the held share.
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "n_routed_experts": REAL, "router_experts": REAL, "held_experts": [0, REAL],
    "zero_expert_num": ZERO, "moe_topk": 3, "routed_scaling_factor": 6,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "num_layers": 2,
    "vocab_size": VOCAB,
}

#: The program's view: TransformerLM's fields.
FIELDS = {
    "num_layers": 4, "d_model": 64, "num_heads": 4, "positions": "rope",
    "rope_theta": 1e7, "mla_kv_rank": 32, "mla_nope_dim": 16,
    "mla_rope_dim": 8, "mla_v_dim": 16, "mla_q_rank": 16,
    "mla_lora_scales": True, "mlp": "moe", "num_experts": REAL,
    "experts_per_token": 3, "expert_dim": 32, "mlp_dim": 96,
    "moe_renormalize": False, "moe_router_bias": True, "moe_scale": 6.0,
    "moe_zero_experts": ZERO, "moe_shortcut": True,
    "tie_embeddings": False, "norm_eps": 1e-5, "attention": "dense",
}


def build(seed: int = 3, **fields):
    """``(module, params)``: the tiny model with its embedding spread to
    unit scale (the initializer's 0.02 would leave the first layer's
    attention flat), the latent's two projections at twice the
    initializer's, and each router's correction bias drawn at the spread
    of its scores (the initializer's zeros would choose nothing)."""
    model = TransformerLM()
    configure(model, {**FIELDS, **fields})
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=seed)
    params = dict(params, embed=params["embed"] * 50.0)
    for i in range(module.num_layers):
        block = dict(params[f"block{i}"])
        block["mla_kv_a"] = {"kernel": block["mla_kv_a"]["kernel"] * 2.0}
        block["mla_kv_b"] = block["mla_kv_b"] * 2.0
        if "router_bias" in block:
            block["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + i), block["router_bias"].shape,
                jnp.float32,
            )
        params[f"block{i}"] = block
    return module, params
