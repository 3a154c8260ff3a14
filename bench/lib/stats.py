"""Order statistics the benchmark reports."""
from __future__ import annotations

import numpy as np


def p95(values) -> float:
    """95th percentile (linear interpolation) of all values."""
    return float(np.percentile(np.asarray(values, np.float64), 95))
