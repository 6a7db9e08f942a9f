"""Peaks of the chip and the least work of the int8 aggregation kernel.

The FLOPs and parameter sizes of a model are its family's
(``bench/families/<family>.py``: ``train_flops_per_item``,
``leaf_sizes``). ``quant_aggregate_cost`` is the least work of one call of
the int8 aggregation kernel: its int8 deltas, block scales and weights read
once and its float32 sum written once.
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


def packed_size(leaf_sizes) -> int:
    """Values per client in the int8 wire layout: each leaf padded to whole
    quantization blocks of 256."""
    return sum(-(-n // QBLOCK) * QBLOCK for n in leaf_sizes)


def quant_aggregate_cost(clients: int, n: int) -> dict:
    """Bytes and FLOPs of one kernel call over ``clients`` x ``n`` values:
    int8 deltas, float32 block scales and weights in, float32 sum out; per
    value and client a dequantizing multiply, a weighting multiply and an
    add."""
    nbytes = clients * n + 4 * clients * n // QBLOCK + 4 * n + 4 * clients
    return {"bytes": nbytes, "flops": 3 * clients * n}
