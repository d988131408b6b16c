"""The comparison that decides ``correct``."""

from __future__ import annotations

import numpy as np


def rel_err(a, b) -> float:
    """max|a - b| / max|b| in float32 on the host: an error relative to the
    reference's largest value (logits of random weights have many near-zero
    entries, where an element-wise relative error means nothing).  inf for
    a shape mismatch or a non-finite value."""
    a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if a32.shape != b32.shape:
        return float("inf")
    if not (np.isfinite(a32).all() and np.isfinite(b32).all()):
        return float("inf")
    return float(np.max(np.abs(a32 - b32)) / (np.max(np.abs(b32)) + 1e-12))
