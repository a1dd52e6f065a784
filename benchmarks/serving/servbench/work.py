"""The model-free half of the yardstick for roofline shares and MFU.

What a model's work is — one decode step's attention operations and bytes,
model FLOPs per token — each model module counts from its own shapes
(``servbench/models/<name>.py``).  What the chip could do with that work is
counted here, from ``peaks.json``.
"""
from __future__ import annotations

from typing import Dict


def roofline_seconds(flops: float, bytes_: float, peaks: Dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["bytes_per_s"])
