"""``reference/mellum2.py``'s comparison on a tiny model: what
``served_logit_gap`` reads for the reference's own greedy tokens, for a few
altered tokens (inside the tenth the quantile leaves out) and for many, and
that ``--with-control`` judges the smallest of its three controls."""

import numpy as np
import pytest

from zkbench import cells

CELL = "mellum2.mixed_lengths_closed"
D, HEADS, KV, HD, F, E, K, VOCAB, S, LAYERS = 32, 4, 2, 8, 16, 8, 2, 64, 48, 4


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    cell = cells.Cell(CELL)
    ref = cell.reference_module()
    model = dict(
        cell.config["model"], hidden_size=D, num_attention_heads=HEADS,
        num_key_value_heads=KV, head_dim=HD, moe_intermediate_size=F,
        num_experts=E, num_experts_per_tok=K, vocab_size=VOCAB,
        num_hidden_layers=LAYERS, sliding_window=8, n_positions=S,
    )
    rng = np.random.default_rng(7)

    def w(*shape):
        return jnp.asarray(
            rng.normal(size=shape).astype(np.float32) * shape[0] ** -0.5
        )

    params = {
        "embed": w(VOCAB, D) * D ** 0.5, "head": w(D, VOCAB) * 4.0,
        "RMSNorm_0": {"scale": jnp.ones(D)},
    }
    for i in range(LAYERS):
        params[f"block{i}"] = {
            "RMSNorm_0": {"scale": jnp.ones(D)}, "RMSNorm_1": {"scale": jnp.ones(D)},
            "qkv": {"kernel": w(D, (HEADS + 2 * KV) * HD)},
            "proj": {"kernel": w(HEADS * HD, D)}, "router": w(D, E),
            "experts_gate": w(D, E * F), "experts_up": w(D, E * F),
            "experts_down": w(F, E * D),
        }

    def greedy(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            padded = np.zeros((S,), np.int32)
            padded[: len(seq)] = seq
            logits = ref.forward(params, model, jnp.asarray(padded))
            seq.append(int(np.argmax(np.asarray(logits)[len(seq) - 1])))
        return np.asarray(seq[len(prompt):], np.int32)

    prompt = rng.integers(0, VOCAB, size=12).astype(np.int32)
    return ref, model, params, {"prompt": prompt, "served": greedy(prompt, 30)}


def test_the_references_own_tokens_read_no_gap(tiny):
    ref, model, params, seq = tiny
    found = ref.served_token_gaps(params, model, [seq], S)
    assert found["tokens_compared"] == 30
    assert found["widest_gap"] == found["max_gap"] == 0.0
    assert found["tokens_not_reference_choice"] == 0


@pytest.mark.parametrize("altered,caught", [(2, False), (12, True)])
def test_the_gap_is_the_one_nine_tokens_in_ten_stay_within(tiny, altered, caught):
    """Two tokens in thirty off the reference's choice lie inside the tenth
    the quantile leaves to routing near-ties (the largest gap still shows
    them); twelve do not."""
    ref, model, params, seq = tiny
    served = seq["served"].copy()
    # the last ones: every token before them still follows the
    # reference's own context
    served[-altered:] = (served[-altered:] + 1) % VOCAB
    found = ref.served_token_gaps(
        params, model, [{"prompt": seq["prompt"], "served": served}], S
    )
    assert found["max_gap"] > 0 and found["tokens_not_reference_choice"] >= altered
    assert (found["widest_gap"] > 0) == caught


def test_with_control_judges_the_smallest_of_its_controls(tiny):
    ref, model, params, seq = tiny
    found = ref.served_token_gaps(params, model, [seq], S, lowp_control=True)
    each = [found[f"control_{name}_widest_gap"] for name in ref.CONTROLS]
    assert len(each) == 3 and found["control_widest_gap"] == min(each)
    # the dropped expert moves every token of a two-expert layer
    assert found["control_expert_dropped_widest_gap"] > 0
