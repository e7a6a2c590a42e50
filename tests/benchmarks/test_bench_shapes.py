"""Each shape function against a hand count at one small shape."""

import pytest

from zkbench import cells

CELL = cells.Cell(cells.load_benchmark()["workloads"][0]["name"])
PEAKS = {"bf16_flops_per_s": 100.0, "int8_ops_per_s": 200.0, "hbm_bytes_per_s": 10.0}

SMALL_NET = {
    "blocks_per_section": [1, 2], "section_features": [4, 8],
    "stem_features": 2, "stem_groups": 2, "image": [8, 8, 3], "num_classes": 5,
}


def by_name(layers):
    return {layer["name"]: layer for layer in layers}


def test_quicknet_layers_by_hand():
    q = CELL.shapes_module("quicknet")
    layers = by_name(q.conv_layers(SMALL_NET))
    # stem0: 8x8x3 -> 4x4x2, 3x3: 4*4*2*9*3 = 864
    assert layers["stem0"]["macs"] == 864 and layers["stem0"]["first"]
    # stem1: 4x4x2 -> 2x2x4, 3x3, 2 groups: 2*2*4*9*(2/2) = 144
    assert layers["stem1"]["macs"] == 144
    # section 0 at 2x2, 4 -> 4 binary 3x3: 2*2*4*9*4 = 576
    assert layers["section0.block0"]["macs"] == 576 and layers["section0.block0"]["binary"]
    # blur: depthwise 3x3/2 on 4 channels to 1x1: 1*1*4*9 = 36
    assert layers["blur1"]["macs"] == 36
    # transition 1x1 4 -> 8 at 1x1: 32; section 1 blocks at 1x1: 8*9*8 = 576
    assert layers["transition1"]["macs"] == 32
    assert layers["section1.block1"]["macs"] == 576
    assert layers["head"]["macs"] == 40
    assert len(layers) == 2 + 1 + 2 + 2 + 1


def test_quicknet_step_operations_by_hand():
    q = CELL.shapes_module("quicknet")
    ops = q.step_operations(SMALL_NET, items=2)
    binary = 3 * 576
    real_first = 864
    real_rest = 144 + 36 + 32 + 40
    assert ops["int8"] == 2 * 2 * binary  # forward only
    # backward of binary (x2), first conv fwd + 1 bwd, the rest fwd + 2 bwd
    assert ops["bf16"] == 2 * 2 * (2 * binary + 2 * real_first + 3 * real_rest)
    least = q.least_step_seconds(SMALL_NET, 2, PEAKS)
    assert least["compute_s"] == pytest.approx(ops["int8"] / 200.0 + ops["bf16"] / 100.0)
    no_head = q.step_operations(SMALL_NET, items=2, convs_only=True)
    assert ops["bf16"] - no_head["bf16"] == 2 * 2 * 3 * 40


def test_quicknet_conv_bytes_by_hand():
    q = CELL.shapes_module("quicknet")
    one = {"blocks_per_section": [1], "section_features": [4], "stem_features": 2,
           "stem_groups": 2, "image": [4, 4, 3], "num_classes": 2}
    # stem0 (first): x 4*4*3=48, y 2*2*2=8, k 3*3*3*2=54
    #   fwd 48*2+8*2+54*2 = 220 ; bwd 2*8*2+48*2+54*2+54*4 = 452 ; no dx
    # stem1: x 8, y 1*1*4=4, k 3*3*1*4=36
    #   fwd 16+8+72 = 96 ; bwd 16+16+72+144 = 248 ; dx 16
    # block (binary): x 4 (1 byte), y 4, k 3*3*4*4=144
    #   fwd 4+8+288 = 300 ; bwd 16+4+288+576 = 884 ; dx 8
    assert q.step_conv_bytes(one, items=1) == 220 + 452 + 96 + 248 + 16 + 300 + 884 + 8


SMALL_LM = {"n_embd": 8, "n_layer": 2, "vocab_size": 10}


def test_transformer_operations_by_hand():
    t = CELL.shapes_module("transformer_lm")
    assert t.matmul_ops_per_token(SMALL_LM) == 24 * 64 * 2
    assert t.head_ops(SMALL_LM) == 2 * 8 * 10
    assert t.attention_ops_at(SMALL_LM, 5) == 4 * 5 * 8 * 2
    # a prompt of 3: contexts 1, 2, 3 -> 4*d*L*(1+2+3) = 4*8*2*6 = 384
    assert t.prompt_attention_ops(SMALL_LM, 3) == 384
    assert t.prompt_ops(SMALL_LM, 3) == 3 * 3072 + 384 + 160
    # two of the three tokens cached: 1 token computed at context 3
    assert t.prompt_ops(SMALL_LM, 3, cached=2) == 3072 + 4 * 3 * 8 * 2 + 160
    assert t.output_token_ops(SMALL_LM, 4) == 3072 + 160 + 4 * 4 * 8 * 2


def test_transformer_bytes_by_hand():
    t = CELL.shapes_module("transformer_lm")
    assert t.weight_bytes(SMALL_LM) == (12 * 64 * 2 + 10 * 8) * 2
    assert t.kv_bytes_per_token(SMALL_LM) == 2 * 8 * 2 * 2
    # contexts 5 and 17 at page 16: 16 + 32 = 48 live tokens
    assert t.live_kv_bytes(SMALL_LM, [5, 17], 16) == 48 * 64
    least = t.least_decode_step_seconds(SMALL_LM, [5, 17], 16, PEAKS)
    assert least["memory_s"] == pytest.approx((3232 + 3072) / 10.0)
    assert least["compute_s"] == pytest.approx(
        (t.output_token_ops(SMALL_LM, 5) + t.output_token_ops(SMALL_LM, 17)) / 100.0
    )
    assert least["least_s"] == max(least["memory_s"], least["compute_s"])
