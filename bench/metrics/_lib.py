"""Shared arithmetic of the metric readers."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import roofline


def p90(values: Sequence[float]) -> float:
    """90th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 90))


def kernel_roofline(facts, kind: str) -> Optional[float]:
    """The least time the chip needs for the work of kernel ``kind``'s calls
    in the traced steps (the larger of their bytes over HBM bandwidth and
    their FLOPs over peak), over the device time of its events, in percent;
    None where the trace holds no event of it. ``kind`` is an op kind that
    the configuration's architecture module times (``KERNELS``)."""
    tr = facts["trace"]
    k = (tr or {}).get("kernels", {}).get(kind)
    if not k or k["calls"] == 0 or k["s"] <= 0:
        return None
    return 100.0 * roofline.roofline_s(k["flops"], k["bytes"],
                                       tr["peaks"]) / k["s"]
