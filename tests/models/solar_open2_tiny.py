"""A Solar-Open2-shaped model at a tiny size for the CPU tests (gated
delta-rule linear attention in three layers of four with no K/V rows,
gated attention without positions in the fourth, a chip's share of the
routed experts beside a shared expert, an untied head), float32, seeded
weights, and the plain reference the benchmark keeps (``benchmarks/
reference/solar_open2.py``, loaded from its file: it imports nothing but
jax and numpy and shares no code with the package)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from zookeeper_tpu import configure
from zookeeper_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("solar_open2_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


VOCAB, POSITIONS = 512, 96
EXPERTS, SHARES = 16, 4  # the router's width; the chips that share a layer
HELD = EXPERTS // SHARES


def model_view(first: int = 0, count: int = HELD):
    """The reference's view of the model: the public config's keys, the
    chip holding experts ``first .. first + count``."""
    return {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_hidden_layers": 4, "vocab_size": VOCAB,
        "moe_intermediate_size": 32, "rms_norm_eps": 1e-5,
        "gqa_layers": [0, 4, 8], "kda_allow_neg_eigval": True,
        "linear_attn_config": {
            "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 3,
            "num_kv_heads": None,
        },
        "n_routed_experts": count, "router_experts": EXPERTS,
        "held_experts": [first, count], "num_experts_per_tok": 4,
        "n_shared_experts": 1,
    }


MODEL = model_view()

#: The program's view: TransformerLM's fields.
FIELDS = {
    "num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "positions": "none",
    "layer_types": ["full", "kda", "kda", "kda"],
    "kda_heads": 3, "kda_head_dim": 16, "kda_gate_rank": 8, "kda_chunk": 8,
    "attention_gate": True, "mlp": "moe", "num_experts": EXPERTS,
    "experts_per_token": 4, "expert_dim": 32, "held_experts": [0, HELD],
    "shared_expert_dim": 32, "tie_embeddings": False, "norm_eps": 1e-5,
    "attention": "dense",
}


def build(seed: int = 3, **fields):
    """``(module, params)``: the tiny model with weights spread so that a
    wrong state moves an argmax (the initializer's zeros for ``dt_bias``
    and ``A_log`` and its 0.02 embedding would leave every channel's decay
    alike), the decay drawn so that a state remembers tens of tokens."""
    model = TransformerLM()
    configure(model, {**FIELDS, **fields})
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    params = dict(params, embed=params["embed"] * 25.0)
    for i, kind in enumerate(FIELDS["layer_types"]):
        if kind != "kda":
            continue
        block = dict(params[f"block{i}"])
        for j, (name, shift) in enumerate(
            (("kda_dt_bias", -3.0), ("kda_A_log", 0.0))
        ):
            k = jax.random.fold_in(key, 16 * i + j)
            block[name] = block[name] + shift + 0.3 * jax.random.normal(
                k, block[name].shape, jnp.float32
            )
        params[f"block{i}"] = block
    return module, params
