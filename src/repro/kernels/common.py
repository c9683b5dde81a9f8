"""Shared Pallas kernel utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """Run kernels in interpret mode unless on a real TPU."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(a, n: int, axis: int, fill):
    """Pad axis up to length n with fill."""
    cur = a.shape[axis]
    if cur == n:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, n - cur)
    return jnp.pad(a, widths, constant_values=fill)


def iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)
