"""Stored rows: the dtype the index keeps its rows in, and the one decode
every reader of them applies.

Coordinates are nonnegative even integers at most U (the paper's Sect. 3.2
normalisation), and every distance is the int32 L1 distance between such
coordinates.  ``IndexConfig.dataset_dtype`` picks how the rows sit in HBM:

* ``int32`` and ``int16``: the coordinates themselves;
* ``uint8``: ``x // 2``, one byte a coordinate, so U may be at most 510
  (``2 * 255``).  For U = 510 and 128 dimensions that is SIFT's (and
  BIGANN's) own 128 bytes a row.

``store_rows`` encodes at build time; ``widen_rows`` turns stored rows back
into int32 coordinates before any L1 is taken.  The decode is chosen from
the dtype at trace time, so int32 and int16 rows compile to the same
program as a bare ``astype(int32)``.  Kernels may import this module; it
imports nothing of ``core``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["STORED_DTYPES", "UINT8_MAX_UNIVERSE", "store_rows", "widen_rows"]

STORED_DTYPES = ("int32", "int16", "uint8")
UINT8_MAX_UNIVERSE = 2 * 255      # uint8 keeps x // 2 of even x <= 510


def store_rows(points: jax.Array, dtype: str) -> jax.Array:
    """(n, m) int coordinates -> the stored form of ``dtype``."""
    if np.dtype(dtype) == np.uint8:
        return (points >> 1).astype(jnp.uint8)
    return points.astype(jnp.dtype(dtype))


def widen_rows(rows: jax.Array) -> jax.Array:
    """Stored rows (any shape) -> int32 coordinates."""
    wide = rows.astype(jnp.int32)
    if rows.dtype == jnp.uint8:
        return wide << 1
    return wide
