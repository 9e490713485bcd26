"""The Llama form: a dense GQA decoder (RMSNorm, rotary embeddings on split
halves, grouped-query causal attention, SwiGLU MLP, untied head).

The architecture module of every configuration that names none (see
``harness.arch_module`` for the interface). An adapter over ``weights.py``
(shapes, key derivation, the served tree), ``reference.py`` (the float32
reference and its float8 control) and ``roofline.py`` (model FLOPs of a
token, the paged decode kernel's FLOPs and bytes).
"""
from __future__ import annotations

from typing import Dict, Tuple

import reference
import roofline
from weights import Arch, make_tree, run_key  # noqa: F401  (the interface)

PAGED_KERNEL = "paged_flash_decode_bkhd"
KERNELS = {PAGED_KERNEL: "paged_decode"}


def arch_of(cfg: Dict, num_layers: int) -> Arch:
    m = cfg["model"]
    return Arch(d_model=m["d_model"], num_heads=m["num_heads"],
                num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
                d_ff=m["d_ff"], vocab=m["vocab_size"],
                padded_vocab=cfg["padded_vocab"], num_layers=num_layers,
                rope_theta=m["rope_theta"], norm_eps=m["norm_eps"])


def served_gaps(a: Arch, key, rung: int, dtype: str, samples, *,
                max_len: int, max_rows: int, mode: str = "f32"
                ) -> Dict[str, float]:
    return reference.served_gaps(a, key, rung, dtype, samples,
                                 control=mode == "fp8", max_len=max_len,
                                 max_rows=max_rows)


def kv_bytes_per_token(a: Arch) -> int:
    return a.kv_bytes_per_token


def step_work(a: Arch, step) -> Tuple[float, Dict[str, Tuple[float, float]]]:
    """Every served token multiplies every weight of the layers and the
    head; the paged decode kernel runs ``step.calls`` times a layer over
    every bound row, at length ``start + c + 1`` for call ``c``."""
    model = 0.0
    for lo, hi in step.useful:
        for p in range(lo, hi):
            model += roofline.token_flops(a.matmul_params, a.num_layers,
                                          a.num_heads, a.head_dim, p)
    flops = bytes_ = 0.0
    for c in range(step.calls):
        f, b = roofline.paged_decode_call([s + c + 1 for s in step.starts],
                                          a.num_heads, a.num_kv_heads,
                                          a.head_dim)
        flops += f * a.num_layers
        bytes_ += b * a.num_layers
    return model, {PAGED_KERNEL: (flops, bytes_)}
