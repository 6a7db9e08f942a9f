"""Operations and bytes of the benchmark's work, from shapes alone.

``train_flops_per_image`` counts the multiply-adds of convolutions and
dense layers (2 FLOPs each): the forward pass, the weight gradients (as many
again) and the input gradients of every layer but the first (nothing needs
the gradient of the images). Biases, activations and pooling are not
counted. ``quant_aggregate_cost`` is the least work of one call of the int8
aggregation kernel: its int8 deltas, block scales and weights read once and
its float32 sum written once.
"""
from __future__ import annotations

import json
import pathlib

QBLOCK = 256

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_flops(published: dict) -> list:
    """Forward FLOPs per image of each weighted layer, input side first."""
    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    out = []
    if "conv_channels" in published:
        k = published["conv_kernel"]
        for ch in published["conv_channels"]:
            out.append(2 * h * w * ch * k * k * c)   # SAME conv, stride 1
            h, w, c = h // 2, w // 2, ch             # 2x2 max pool
        d = h * w * c
        fc = published["fc_width"]
        out += [2 * d * fc, 2 * fc * classes]
    else:
        d = h * w * c
        width = published["hidden_width"]
        for _ in range(published["hidden_layers"]):
            out.append(2 * d * width)
            d = width
        out.append(2 * d * classes)
    return out


def forward_flops_per_image(published: dict) -> int:
    return sum(layer_flops(published))


def train_flops_per_image(published: dict) -> int:
    """Forward + weight gradients + input gradients past the first layer."""
    per = layer_flops(published)
    return 3 * sum(per) - per[0]


def n_params(published: dict) -> int:
    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    if "conv_channels" in published:
        k, total = published["conv_kernel"], 0
        for ch in published["conv_channels"]:
            total += k * k * c * ch + ch
            h, w, c = h // 2, w // 2, ch
        fc = published["fc_width"]
        return total + h * w * c * fc + fc + fc * classes + classes
    d, width, total = h * w * c, published["hidden_width"], 0
    for _ in range(published["hidden_layers"]):
        total += d * width + width
        d = width
    return total + d * classes + classes


def leaf_sizes(published: dict) -> list:
    """Sizes of the weight and bias leaves, as the packed layout pads them."""
    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    out = []
    if "conv_channels" in published:
        k = published["conv_kernel"]
        for ch in published["conv_channels"]:
            out += [k * k * c * ch, ch]
            h, w, c = h // 2, w // 2, ch
        fc = published["fc_width"]
        return out + [h * w * c * fc, fc, fc * classes, classes]
    d, width = h * w * c, published["hidden_width"]
    for _ in range(published["hidden_layers"]):
        out += [d * width, width]
        d = width
    return out + [d * classes, classes]


def packed_size(published: dict) -> int:
    """Values per client in the int8 wire layout: each leaf padded to whole
    quantization blocks of 256."""
    return sum(-(-n // QBLOCK) * QBLOCK for n in leaf_sizes(published))


def quant_aggregate_cost(clients: int, n: int) -> dict:
    """Bytes and FLOPs of one kernel call over ``clients`` x ``n`` values:
    int8 deltas, float32 block scales and weights in, float32 sum out; per
    value and client a dequantizing multiply, a weighting multiply and an
    add."""
    nbytes = clients * n + 4 * clients * n // QBLOCK + 4 * n + 4 * clients
    return {"bytes": nbytes, "flops": 3 * clients * n}
