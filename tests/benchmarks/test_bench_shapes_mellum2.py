"""``shapes/mellum2.py`` against hand counts at one small shape, and the
published widths against ISSUE 26's arithmetic."""

import pytest

from zkbench import cells

CELL = cells.Cell("mellum2.mixed_lengths_closed")
S = CELL.shapes_module("mellum2")
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

#: d 8, 2 query heads over 1 key/value head of 4, 4 experts of width 3,
#: top 2, 3 layers (window, full, window), window 5, vocabulary 11.
SMALL = {
    "hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
    "head_dim": 4, "num_hidden_layers": 3, "vocab_size": 11,
    "layer_types": ["sliding_attention", "full_attention", "sliding_attention", "full_attention"],
    "sliding_window": 5, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 3,
}


def test_products_by_hand():
    assert S.widths(SMALL) == (8, 4)
    assert S.window_layers(SMALL) == [True, False, True]
    # experts: 6 k d f = 6*2*8*3
    assert S.expert_ops_per_token(SMALL) == 288
    # a layer: qkv 2*8*(8+8) = 256, proj 2*8*8 = 128, router 2*8*4 = 64
    assert S.matmul_ops_per_token(SMALL) == 3 * (256 + 128 + 64 + 288)
    assert S.head_ops(SMALL) == 2 * 8 * 11


def test_attention_counts_the_band_in_window_layers():
    # context 3: inside the window everywhere: 3 layers x 3 keys
    assert S.keys_attended(SMALL, 3) == 9
    assert S.attention_ops_at(SMALL, 3) == 4 * 8 * 9
    # context 9: the two window layers attend 5, the full layer 9
    assert S.keys_attended(SMALL, 9) == 19
    # a prompt of 7: full 7*8/2 = 28; window 5*6/2 + 2*5 = 25
    assert S.prompt_attention_ops(SMALL, 7) == 4 * 8 * (28 + 25 + 25)
    # a prompt is the sum of its tokens
    assert S.prompt_attention_ops(SMALL, 7) == sum(
        S.attention_ops_at(SMALL, c) for c in range(1, 8)
    )
    assert S.prompt_ops(SMALL, 7) == (
        7 * S.matmul_ops_per_token(SMALL) + S.prompt_attention_ops(SMALL, 7)
        + S.head_ops(SMALL)
    )
    assert S.output_token_ops(SMALL, 9) == (
        S.matmul_ops_per_token(SMALL) + S.head_ops(SMALL) + 4 * 8 * 19
    )


def test_bytes_by_hand():
    # a key and a value of 4 elements, 2 bytes each, a layer
    assert S.kv_bytes_per_token(SMALL) == 16
    # lengths 3 and 9 at page 4: live 4 and 12; window layers cap at 5
    assert S.live_kv_bytes(SMALL, [3, 9], 4) == ((4 + 4 + 4) + (5 + 12 + 5)) * 16
    # one token touches 2 of the 4 experts, three tokens all of them
    assert S.expert_bytes(SMALL, 1) == 3 * 2 * 8 * 3 * 2
    assert S.expert_bytes(SMALL, 3) == S.expert_bytes(SMALL, 64) == 3 * 4 * 8 * 3 * 2
    dense = 8 * (8 + 8) + 8 * 8 + 8 * 4
    assert S.weight_bytes(SMALL, 1) == 3 * (dense * 2 + 288) + 11 * 8 * 2
    least = S.least_decode_step_seconds(SMALL, [3, 9], 4, PEAKS)
    assert least["memory_s"] == pytest.approx(
        (S.weight_bytes(SMALL, 2) + S.live_kv_bytes(SMALL, [3, 9], 4)) / 10.0
    )
    assert least["least_s"] == max(least["compute_s"], least["memory_s"])
    # experts of one call: compute 2*288/100 against memory 576/10, 3 layers
    assert S.least_expert_seconds(SMALL, 2, PEAKS) == pytest.approx(3 * 57.6)


def test_published_widths_match_the_issue():
    model = CELL.config["model"]
    # 99 M of a token's operations a layer are the experts' (ISSUE 26)
    assert S.expert_ops_per_token(model) == pytest.approx(99.1e6, rel=1e-3)
    assert S.matmul_ops_per_token(model) / 8 == pytest.approx(141.9e6, rel=1e-3)
    # 2,048 bytes a token a layer
    assert S.kv_bytes_per_token(model) == 2048
    # a decode step of 64 tokens reads all 6.3 GB of expert weights
    assert 8 * S.expert_bytes(model, 64) == pytest.approx(6.34e9, rel=1e-2)
    assert S.window_layers(model) == [True, True, True, False] * 2


def test_the_file_holds_the_sources_keys_at_its_top_level():
    """The driver compares the top level of the file with the source's
    ``config.json``; the entry and the reference read ``model``. Both say
    the same, and the depth is the one key that ``reduced`` names."""
    config, model = CELL.config, CELL.config["model"]
    assert set(model) - set(config) == {"n_positions"}
    assert all(config[key] == model[key] for key in model if key != "n_positions")
    (entry,) = [c for c in cells.load_benchmark()["configs"] if c["name"] == "mellum2_8l"]
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced_from_source"])
    published = config["published"]
    assert published["num_hidden_layers"] == len(config["layer_types"]) == 28
    changed = [key for key in published if config[key] != published[key]]
    assert changed == entry["reduced"] and config["num_hidden_layers"] == 8
    assert config["program"]["model.num_layers"] == config["num_hidden_layers"]
