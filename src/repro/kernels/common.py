"""Shared kernel helpers."""

from __future__ import annotations

import jax
import numpy as np

from repro.utils import ceil_div, round_up  # noqa: F401  (re-export)


def use_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


_INT_FOR_SIZE = {1: np.int8, 2: np.int16, 4: np.int32, 8: np.int64}


def bitcast_to_int(x: jax.Array) -> jax.Array:
    """Bitwise view of ``x`` as a signed int of the same width.

    Bitwise (not value) comparison is what delta detection needs: NaN payload
    changes count as changes, -0.0 vs +0.0 count as changes — matching what a
    byte-level CMI hash would say. Signed, because Mosaic (the TPU kernel
    compiler) compares no unsigned words.
    """
    target = _INT_FOR_SIZE[np.dtype(x.dtype).itemsize]
    if np.dtype(x.dtype) == target:
        return x
    return jax.lax.bitcast_convert_type(x, target)
