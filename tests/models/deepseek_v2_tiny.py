"""A DeepSeek-V2-shaped model at a tiny size for the CPU tests (latent
attention in every layer, one dense SwiGLU layer ahead of layers of routed
experts with shared ones, softmax weights used as they are, DeepSeek's
YaRN), float32, seeded weights, and the plain reference the benchmark
keeps (``benchmarks/reference/deepseek_v2.py``, loaded from its file: it
imports nothing but jax and numpy and shares no code with the package)."""

import importlib.util
import os

from zookeeper_tpu import configure
from zookeeper_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("deepseek_v2_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


VOCAB, POSITIONS = 512, 96

#: The reference's view of the model: the public config's keys.
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "num_hidden_layers": 3,
    "vocab_size": VOCAB,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn",
    },
}

#: The program's view: TransformerLM's fields.
FIELDS = {
    "num_layers": 3, "d_model": 64, "num_heads": 4, "positions": "rope",
    "rope_theta": 10000.0, "yarn_factor": 40.0, "yarn_original_len": 4096,
    "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
    "yarn_mscale_all_dim": 0.707, "mla_kv_rank": 32, "mla_nope_dim": 16,
    "mla_rope_dim": 8, "mla_v_dim": 16, "mlp": "moe", "num_experts": 8,
    "experts_per_token": 2, "expert_dim": 32, "shared_expert_dim": 64,
    "dense_layers": 1, "mlp_dim": 96, "moe_renormalize": False,
    "tie_embeddings": False, "norm_eps": 1e-6, "attention": "dense",
}


def build(seed: int = 3, **fields):
    """``(module, params)``: the tiny model with its embedding spread to
    unit scale (the initializer's 0.02 would leave the first layer's
    attention flat) and the latent's two projections at twice the
    initializer's, so that attention, the rotation and the latent's norm
    each move the logits."""
    model = TransformerLM()
    configure(model, {**FIELDS, **fields})
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=seed)
    params = dict(params, embed=params["embed"] * 50.0)
    for i in range(FIELDS["num_layers"]):
        block = dict(params[f"block{i}"])
        block["mla_kv_a"] = {"kernel": block["mla_kv_a"]["kernel"] * 2.0}
        block["mla_kv_b"] = block["mla_kv_b"] * 2.0
        params[f"block{i}"] = block
    return module, params
