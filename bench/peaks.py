"""Chip peaks and the least work the agent arena's calls require.

``peaks.json`` holds one entry per ``device_kind``; a device that is not
in it is an error, not a default. The arena's bytes and operations are
counted from the agent rows actually predicted or updated (never the
power-of-two padding the engine dispatches): a row is one agent's
``(n_classes, dim + 1)`` float32 weight block.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

F32 = 4


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def predict_work(n_classes: int, dim: int) -> Tuple[int, int]:
    """(bytes, ops) of one agent's predict: read its weights; one
    multiply-add per weight."""
    block = n_classes * (dim + 1)
    return F32 * block, 2 * block


def update_work(n_classes: int, dim: int) -> Tuple[int, int]:
    """(bytes, ops) of one agent's CSOAA update: read w, g2, x and the
    cost vector, write w and g2. Operations per weight: the prediction's
    multiply-add, the gradient product, the AdaGrad square-add, and the
    square root, add, divide, scale and subtract of the step; plus one
    subtraction per class for the error."""
    block = n_classes * (dim + 1)
    nbytes = F32 * (2 * block + dim + n_classes + 2 * block)
    return nbytes, 10 * block + n_classes


def least_seconds(nbytes: float, ops: float, peak: Dict[str, float]) -> float:
    """Roofline: the larger of bytes over HBM bandwidth and operations
    over the chip's peak rate."""
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
