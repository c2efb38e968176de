"""Row-major flattening of 2-D arrays, written as gathers.

The TPU compiler lowers the flattening reshape of an (N, K) array whose K
is not a multiple of 128 lanes (the KNN graph's K=150) to a relayout
whose compile time grows with N: about a minute per reshape at N=70,000.
A gather by the (row, column) of every entry moves the same values and
compiles in under a second.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def row_major(n: int, k: int) -> tuple[jax.Array, jax.Array]:
    """(row, column) of every entry of an (n, k) array in row-major
    order; the rows alone are ``repeat(arange(n), k)``."""
    e = jnp.arange(n * k, dtype=jnp.int32)
    return e // k, e % k


def ravel_rows(x: jax.Array) -> jax.Array:
    """``x.reshape(-1)`` of a 2-D array."""
    r, c = row_major(*x.shape)
    return x[r, c]
