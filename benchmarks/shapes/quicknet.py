"""Operations and bytes of one QuickNet training step, from shapes alone.

A roofline reads the same needed work whatever implements the kernel, so
these counts never come from the compiler (whose ``cost_analysis`` counts
nothing for a Pallas call and whole operand buffers as bytes, PERF.md).

A convolution with output ``[H, W, Cout]`` per image, kernel ``k x k``,
``Cin`` input channels and ``g`` groups does ``H*W*Cout*k*k*Cin/g``
multiply-accumulates per image forward, i.e. twice that in operations.
Backward it does the same again for the input's gradient (not for the
first convolution, whose input is the image) and the same again for the
kernel's gradient. Recomputation is not counted.

The binary convolutions' forward runs on +-1 operands: its least time is
over the int8 peak. Their backward multiplies real-valued gradients and
cannot: it is counted against the bf16 peak, like every other operation.

Layers, for sections of ``n_s`` blocks and widths ``f_s`` at an
``S x S`` image (QuickNet-Large: n = 6, 8, 12, 6; f = 64, 128, 256, 512;
S = 224):

- stem: 3x3/2 conv 3 -> 8 at S/2, grouped (4) 3x3/2 conv 8 -> f_0 at S/4;
- section s at S/4/2^s: n_s binary 3x3 convs f_s -> f_s;
- transition into section s > 0: depthwise 3x3/2 blur-pool on f_(s-1)
  channels (9 MACs an output element), 1x1 conv f_(s-1) -> f_s;
- head: dense f_3 -> classes.

BatchNorm, ReLU, sign, residual adds and the loss are a few operations per
activation element, under 1% of the convolutions', and are left out: the
count is a lower bound of the needed work, so a share of the peak computed
from it cannot be flattered.
"""

from typing import Dict, List


def conv_layers(model: Dict) -> List[Dict]:
    """Every convolution and the dense head: ``macs`` per image forward,
    ``binary``, ``first`` (no input gradient), and the element counts of
    input, output and kernel per image (``in_elems``, ``out_elems``,
    ``kernel_elems``: the kernel's is per step, not per image)."""
    size, _, channels = model["image"]
    feats = model["section_features"]
    layers = []

    def conv(name, hw, cin, cout, k, groups=1, binary=False, first=False, hw_in=None):
        hw_in = hw if hw_in is None else hw_in
        layers.append({
            "name": name,
            "macs": hw * hw * cout * k * k * cin // groups,
            "binary": binary,
            "first": first,
            "in_elems": hw_in * hw_in * cin,
            "out_elems": hw * hw * cout,
            "kernel_elems": k * k * cin // groups * cout,
        })

    s2, s4 = size // 2, size // 4
    conv("stem0", s2, channels, model["stem_features"], 3, first=True, hw_in=size)
    conv("stem1", s4, model["stem_features"], feats[0], 3,
         groups=model["stem_groups"], hw_in=s2)
    hw = s4
    for s, (n, f) in enumerate(zip(model["blocks_per_section"], feats)):
        if s > 0:
            prev = feats[s - 1]
            conv(f"blur{s}", hw // 2, prev, prev, 3, groups=prev, hw_in=hw)
            hw //= 2
            conv(f"transition{s}", hw, prev, f, 1)
        for b in range(n):
            conv(f"section{s}.block{b}", hw, f, f, 3, binary=True)
    layers.append({
        "name": "head", "macs": feats[-1] * model["num_classes"],
        "binary": False, "first": False, "in_elems": feats[-1],
        "out_elems": model["num_classes"],
        "kernel_elems": feats[-1] * model["num_classes"], "dense": True,
    })
    return layers


def step_operations(model: Dict, items: int, convs_only: bool = False) -> Dict[str, float]:
    """Operations of one training step over ``items`` images, split by the
    peak they are held to: ``int8`` (binary forward) and ``bf16`` (all the
    rest, forward and backward)."""
    int8 = bf16 = 0.0
    for layer in conv_layers(model):
        if convs_only and layer.get("dense"):
            continue
        fwd = 2.0 * layer["macs"] * items
        bwd = fwd * (1 if layer["first"] else 2)
        if layer["binary"]:
            int8 += fwd
            bf16 += bwd
        else:
            bf16 += fwd + bwd
    return {"int8": int8, "bf16": bf16}


def step_conv_bytes(model: Dict, items: int) -> float:
    """Bytes the step's convolutions must move at the least: forward each
    reads its input (1 byte an element where it is +-1, else 2) and its
    kernel and writes its output (2 bytes); backward each reads the output's
    gradient twice (2 bytes) and its input and kernel once, and writes the
    input's gradient (2 bytes) and the kernel's (4 bytes)."""
    total = 0.0
    for layer in conv_layers(model):
        if layer.get("dense"):
            continue
        in_b = 1 if layer["binary"] else 2
        x, y, k = layer["in_elems"], layer["out_elems"], layer["kernel_elems"]
        total += items * (x * in_b + y * 2) + k * 2  # forward
        total += items * (2 * y * 2 + x * in_b) + k * 2 + k * 4  # backward
        if not layer["first"]:
            total += items * x * 2
    return total


def least_step_seconds(model: Dict, items: int, peaks: Dict, convs_only: bool = False) -> Dict[str, float]:
    ops = step_operations(model, items, convs_only)
    compute = ops["int8"] / peaks["int8_ops_per_s"] + ops["bf16"] / peaks["bf16_flops_per_s"]
    memory = step_conv_bytes(model, items) / peaks["hbm_bytes_per_s"]
    return {"compute_s": compute, "memory_s": memory, **ops}
