"""1-D key derivation for 2-D spatial points (paper §3.2).

The paper projects (x, y) to a sort key via "either one arbitrary axis or
some aggregated value (e.g., Z-order curve and GeoHash)". We implement:

  * ``morton`` (default) — bit-interleaved Z-order code over quantized
    coordinates. Morton codes are jointly monotone: x1<=x2 and y1<=y2
    implies z(x1,y1) <= z(x2,y2), so the key interval
    [z(rect_lo), z(rect_hi)] covers every point of an axis-aligned rect
    (with false positives that the refine phase removes) — exactly the
    filter+refine contract the paper's range query relies on.
  * ``x`` / ``y`` — single-axis keys.

TPU adaptation notes (DESIGN.md §2): keys are kept at <= 24 total bits so
their float32 image is EXACT (f32 has a 24-bit mantissa); all spline /
radix arithmetic then incurs no key-rounding error. Default is 11 bits per
dimension (22-bit Morton key), leaving 10 bits of headroom for a partition
id in a single uint32 composite sort key (paper's re-partition shuffle is
realized as one global radix sort).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

# Default geometry of the key space.
DEFAULT_BITS_PER_DIM = 11          # 22-bit morton keys, exact in float32
MAX_BITS_PER_DIM = 12              # 24-bit morton keys, still exact in f32


@dataclasses.dataclass(frozen=True)
class KeySpec:
    """How 2-D points are projected to 1-D sort keys."""

    kind: str = "morton"           # 'morton' | 'x' | 'y'
    bits_per_dim: int = DEFAULT_BITS_PER_DIM
    # Data-space bounds used for quantization: (xlo, ylo, xhi, yhi).
    bounds: Tuple[float, float, float, float] = (0.0, 0.0, 1.0, 1.0)

    @property
    def key_bits(self) -> int:
        if self.kind == "morton":
            return 2 * self.bits_per_dim
        return self.bits_per_dim

    @property
    def sentinel(self) -> int:
        """Padding key, strictly greater than every valid key."""
        return 1 << self.key_bits

    def __post_init__(self):
        if self.kind not in ("morton", "x", "y"):
            raise ValueError(f"unknown key kind {self.kind!r}")
        if self.kind == "morton" and self.bits_per_dim > MAX_BITS_PER_DIM:
            raise ValueError(
                "morton keys above 24 total bits are not exact in float32")


def quantize(coord, lo, hi, bits: int):
    """Map float coords in [lo, hi] to integers in [0, 2^bits - 1].

    With static bounds the scale is one float32 constant divided on the
    host, so an encode run op by op (the build, inserts) and one traced
    into a compiled program (the read prep) agree bit for bit: XLA folds
    a constant division on the host, and a TPU's own float32 division
    does not always round as the host's does. A point lookup matches
    only on an equal key."""
    span = hi - lo
    if isinstance(span, (int, float, np.number)):
        scale = float(np.float32(1 << bits) / np.float32(max(span, 1e-30)))
    else:
        scale = (1 << bits) / jnp.maximum(span, 1e-30)
    q = jnp.floor((coord - lo) * scale)
    return jnp.clip(q, 0, (1 << bits) - 1).astype(jnp.uint32)


def spread_bits(v):
    """Spread the low 16 bits of ``v`` to even bit positions (uint32)."""
    v = v.astype(jnp.uint32)
    v = (v | (v << 8)) & jnp.uint32(0x00FF00FF)
    v = (v | (v << 4)) & jnp.uint32(0x0F0F0F0F)
    v = (v | (v << 2)) & jnp.uint32(0x33333333)
    v = (v | (v << 1)) & jnp.uint32(0x55555555)
    return v


def compact_bits(v):
    """Inverse of :func:`spread_bits` (for tests / decoding)."""
    v = v.astype(jnp.uint32) & jnp.uint32(0x55555555)
    v = (v | (v >> 1)) & jnp.uint32(0x33333333)
    v = (v | (v >> 2)) & jnp.uint32(0x0F0F0F0F)
    v = (v | (v >> 4)) & jnp.uint32(0x00FF00FF)
    v = (v | (v >> 8)) & jnp.uint32(0x0000FFFF)
    return v


def morton_encode(qx, qy):
    """Interleave quantized coords: x gets even bits, y odd bits."""
    return spread_bits(qx) | (spread_bits(qy) << jnp.uint32(1))


def morton_decode(key):
    return compact_bits(key), compact_bits(key >> jnp.uint32(1))


def make_keys(x, y, spec: KeySpec):
    """Project float point coords to uint32 sort keys per ``spec``."""
    xlo, ylo, xhi, yhi = spec.bounds
    if spec.kind == "morton":
        qx = quantize(x, xlo, xhi, spec.bits_per_dim)
        qy = quantize(y, ylo, yhi, spec.bits_per_dim)
        return morton_encode(qx, qy)
    if spec.kind == "x":
        return quantize(x, xlo, xhi, spec.bits_per_dim)
    return quantize(y, ylo, yhi, spec.bits_per_dim)


def rect_key_range(rect, spec: KeySpec):
    """[key_lo, key_hi] covering every point inside rect=(xl,yl,xh,yh).

    Valid because morton codes (and axis keys) are monotone in each
    coordinate; see module docstring.
    """
    xl, yl, xh, yh = rect[..., 0], rect[..., 1], rect[..., 2], rect[..., 3]
    xlo, ylo, xhi, yhi = spec.bounds
    if spec.kind == "morton":
        klo = morton_encode(quantize(xl, xlo, xhi, spec.bits_per_dim),
                            quantize(yl, ylo, yhi, spec.bits_per_dim))
        khi = morton_encode(quantize(xh, xlo, xhi, spec.bits_per_dim),
                            quantize(yh, ylo, yhi, spec.bits_per_dim))
    elif spec.kind == "x":
        klo = quantize(xl, xlo, xhi, spec.bits_per_dim)
        khi = quantize(xh, xlo, xhi, spec.bits_per_dim)
    else:
        klo = quantize(yl, ylo, yhi, spec.bits_per_dim)
        khi = quantize(yh, ylo, yhi, spec.bits_per_dim)
    return klo, khi


def keys_to_f32(keys):
    """Exact float32 image of (<=24 bit) integer keys."""
    return keys.astype(jnp.float32)


def z_split_intervals(qxl, qyl, qxh, qyh, valid, *, depth: int = 2):
    """Decompose a quantized rect's morton interval (BIGMIN-style).

    The naive interval [z(lo), z(hi)] includes Z-curve detours outside
    the rect; splitting the rect at the most-significant differing
    morton bit removes the largest detour. ``depth`` recursive splits
    yield up to 2^depth DISJOINT subintervals that still jointly cover
    every in-rect key — the refine phase stays exact while the learned
    scan windows shrink by orders of magnitude (beyond-paper
    optimization; EXPERIMENTS.md §Perf spatial iteration 3).

    Inputs are (...,) uint32 quantized corners + validity. Returns
    (zlo, zhi, piece_valid) with a leading 2^depth axis folded into a
    new trailing dimension: shapes (..., 2^depth).
    """
    def msb_position(v):
        """Highest set bit position of uint32 (0 -> 0); integer-exact."""
        v = v.astype(jnp.uint32)
        v = v | (v >> 1)
        v = v | (v >> 2)
        v = v | (v >> 4)
        v = v | (v >> 8)
        v = v | (v >> 16)
        # popcount via SWAR
        v = v - ((v >> 1) & jnp.uint32(0x55555555))
        v = (v & jnp.uint32(0x33333333)) + ((v >> 2) &
                                            jnp.uint32(0x33333333))
        v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
        pc = (v * jnp.uint32(0x01010101)) >> 24
        return jnp.maximum(pc.astype(jnp.int32) - 1, 0)

    pieces = [(qxl, qyl, qxh, qyh, valid)]
    for _ in range(depth):
        nxt = []
        for (xl, yl, xh, yh, v) in pieces:
            zl = morton_encode(xl, yl)
            zh = morton_encode(xh, yh)
            diff = zl ^ zh
            msb = msb_position(diff)
            even = (msb % 2) == 0          # even bits carry x
            b = (msb // 2).astype(jnp.uint32)
            hbx = (xh >> b) << b
            hby = (yh >> b) << b
            nosplit = diff == 0
            x1h = jnp.where(nosplit, xh,
                            jnp.where(even, hbx - 1, xh))
            y1h = jnp.where(nosplit, yh,
                            jnp.where(even, yh, hby - 1))
            x2l = jnp.where(even, hbx, xl)
            y2l = jnp.where(even, yl, hby)
            nxt.append((xl, yl, x1h.astype(jnp.uint32),
                        y1h.astype(jnp.uint32), v))
            nxt.append((x2l.astype(jnp.uint32), y2l.astype(jnp.uint32),
                        xh, yh, v & ~nosplit))
        pieces = nxt
    zlo = jnp.stack([morton_encode(p[0], p[1]) for p in pieces], -1)
    zhi = jnp.stack([morton_encode(p[2], p[3]) for p in pieces], -1)
    pv = jnp.stack([p[4] for p in pieces], -1)
    return zlo, zhi, pv


def data_bounds(x, y, pad_frac: float = 1e-6):
    """Host helper: tight data bounds, padded so max coords quantize inside."""
    x = np.asarray(x)
    y = np.asarray(y)
    xlo, xhi = float(x.min()), float(x.max())
    ylo, yhi = float(y.min()), float(y.max())
    dx = max(xhi - xlo, 1e-12) * pad_frac
    dy = max(yhi - ylo, 1e-12) * pad_frac
    return (xlo, ylo, xhi + dx, yhi + dy)
