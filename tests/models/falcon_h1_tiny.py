"""A Falcon-H1-shaped model at a tiny size for the CPU tests (a Mamba-2
state-space mixer beside grouped attention in every block, a dense gated
MLP, an untied head, every multiplier the program has off 1), float32, seeded weights, and
the plain reference the benchmark keeps (``benchmarks/reference/
falcon_h1.py``, loaded from its file: it imports nothing but jax and
numpy and shares no code with the package)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from zookeeper_tpu import configure
from zookeeper_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "falcon_h1.py")
    spec = importlib.util.spec_from_file_location("falcon_h1_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


VOCAB, POSITIONS = 512, 96

#: The reference's view of the model: the public config's keys.
MODEL = {
    "hidden_size": 64, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 3, "vocab_size": VOCAB,
    "intermediate_size": 96, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "embedding_multiplier": 5.657, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.6,
    "key_multiplier": 0.7, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 0.9,
    "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
    "mlp_multipliers": [0.18, 1.5],
}

#: The program's view: TransformerLM's fields.
FIELDS = {
    "num_layers": 3, "d_model": 64, "num_heads": 6, "num_kv_heads": 2,
    "head_dim": 16, "positions": "rope", "rope_theta": 1e11,
    "mlp": "swiglu", "mlp_dim": 96, "tie_embeddings": False,
    "norm_eps": 1e-5, "attention": "dense",
    "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16, "ssm_groups": 2,
    "ssm_chunk": 8,
    **{
        k: MODEL[k] for k in (
            "embedding_multiplier", "lm_head_multiplier",
            "attention_out_multiplier",
            "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
            "ssm_multipliers", "mlp_multipliers",
        )
    },
}


def build(seed: int = 3, **fields):
    """``(module, params)``: the tiny model with weights spread so that a
    wrong state moves an argmax (the initializer's zeros for the
    convolution's bias, ``dt_bias``, ``A_log`` and its 0.02 embedding
    would leave half the mixer idle)."""
    model = TransformerLM()
    configure(model, {**FIELDS, **fields})
    module = model.build((POSITIONS,), VOCAB)
    params, _ = model.initialize(module, (POSITIONS,), seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    params = dict(params, embed=params["embed"] * 25.0)
    for i in range(FIELDS["num_layers"]):
        block = dict(params[f"block{i}"])
        for j, name in enumerate(("ssm_conv_bias", "dt_bias", "A_log", "D")):
            k = jax.random.fold_in(key, 16 * i + j)
            block[name] = block[name] + 0.3 * jax.random.normal(
                k, block[name].shape, jnp.float32
            )
        params[f"block{i}"] = block
    return module, params
