"""Oracle: the numpy delta+zigzag used by the live tracing pipeline."""

from __future__ import annotations

import numpy as np

from ...core.timestamps import delta_zigzag_encode


def delta_zigzag_ref(ticks: np.ndarray) -> np.ndarray:
    """ticks: flat u32 -> zigzag u32 (delegates to core.timestamps)."""
    flat = np.asarray(ticks, np.uint32).reshape(-1, 2) \
        if ticks.ndim == 1 and ticks.size % 2 == 0 \
        else np.asarray(ticks, np.uint32).reshape(-1, 1)
    out = delta_zigzag_encode(flat.reshape(-1, flat.shape[-1]))
    return out


def uvarint_planes_ref(values: np.ndarray):
    """u64 values -> (byte counts, (10, n) byte planes); the numpy mirror
    of the varint kernels (delegates to core.encode_backend)."""
    from ...core.encode_backend import _uvarint_planes_np
    return _uvarint_planes_np(np.asarray(values, np.uint64))

