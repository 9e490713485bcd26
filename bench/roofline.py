"""Peaks of the chip and the work of each measured kernel and step.

Peaks of one TPU v5e chip, from Google Cloud's documentation ("TPU v5e",
system architecture): 197 TFLOP/s in bfloat16, 819 GB/s of HBM bandwidth,
16 GB of HBM. JAX names the chip ``TPU v5 lite``. A kind missing from the
table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind]


def paged_decode_call(lengths: Iterable[int], num_heads: int,
                      num_kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one call of the paged decode kernel needs for one
    layer: every row reads the K and V of its ``length`` live tokens and
    its query, and writes its output; scores and the weighted sum cost
    2 FLOPs per multiply-add. Rows of length 0 do nothing."""
    flops = bytes_ = 0.0
    for n in lengths:
        if n <= 0:
            continue
        flops += 4.0 * num_heads * head_dim * n
        bytes_ += itemsize * (2.0 * num_kv_heads * head_dim * n
                              + 2.0 * num_heads * head_dim)
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take for this work."""
    return max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])


def token_flops(matmul_params: int, num_layers: int, num_heads: int,
                head_dim: int, position: int) -> float:
    """Model FLOPs to run one token at ``position`` (0-based) through the
    step: 2 per weight it multiplies, and causal attention over the
    ``position + 1`` tokens up to it in every layer."""
    return 2.0 * matmul_params \
        + 4.0 * num_layers * num_heads * head_dim * (position + 1)
