"""Shared arithmetic of the metric readers."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def p90(values: Sequence[float]) -> float:
    """90th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 90))
