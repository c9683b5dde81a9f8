"""Pallas TPU kernel: learned lower-bound search (paper Fig. 3).

Per query key: radix-table bucket -> knot window [T[j], T[j+1]] ->
compare-count segment locate -> linear interpolation -> eps-bounded
probe window compare-count over the sorted key array.

Memory layout (per grid step):
  queries     (1, QB)   SMEM, blocked over the grid
  fscal       (2,)      SMEM [kmin, scale]
  iscal       (2,)      SMEM [n_knots, count]
  knot keys   (M,)      SMEM, whole array
  knot pos    (M,)      SMEM, whole array
  radix table (R,)      SMEM, whole array (R = 2^b + 2)
  keys        (1, N')   VMEM, the whole sorted key row; partitions are
                        sized at build so N*4B fits VMEM — the HBM->VMEM
                        copy is amortized over the query batch.

The radix locate, segment search and interpolation are scalar work on
SMEM values, one query at a time. The probe window is the one vector
step: a load of W = round_up(probe, 128) + 128 lanes from the
128-aligned lane at or below the window start (Mosaic only takes
lane-aligned dynamic slices), masked to [start, start + probe). The
key row is padded by the wrapper so that load never runs past it.
Results collect in a (1, QB) vector and are stored once per block.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, iota2

QBLOCK = 128
LANE = 128


def window_lanes(probe: int) -> int:
    """Lanes loaded per probe: the window plus its misalignment."""
    return cdiv(probe, LANE) * LANE + LANE


def _kernel(fscal_ref, iscal_ref, q_ref, kk_ref, kp_ref, rt_ref,
            keys_ref, out_ref, *, probe: int, radix_bits: int,
            n_pad: int):
    kmin = fscal_ref[0]
    scale = fscal_ref[1]
    n_knots = iscal_ref[0]
    count = iscal_ref[1]
    m_pad = kk_ref.shape[0]
    w = window_lanes(probe)
    lane = iota2((1, w), 1)
    olane = iota2((1, QBLOCK), 1)

    def one(i, acc):
        q = q_ref[0, i]
        # --- radix locate ---
        j = jnp.clip(jnp.floor((q - kmin) * scale), 0.0,
                     float(1 << radix_bits)).astype(jnp.int32)
        lo = rt_ref[j]
        hi = jnp.clip(rt_ref[j + 1], lo, jnp.maximum(n_knots - 1, 0))
        # --- segment search over the knot window [lo, hi] ---
        succ = jax.lax.fori_loop(
            lo, hi + 1, lambda m, c: c + (kk_ref[m] < q).astype(jnp.int32),
            lo)
        seg = jnp.maximum(succ - 1, 0)
        nxt = jnp.minimum(seg + 1, m_pad - 1)
        k0, k1 = kk_ref[seg], kk_ref[nxt]
        p0, p1 = kp_ref[seg], kp_ref[nxt]
        t = jnp.clip((q - k0) / jnp.maximum(k1 - k0, 1e-30), 0.0, 1.0)
        phat = p0 + t * (p1 - p0)
        # --- eps-bounded probe (exact lower bound) ---
        start = jnp.clip(jnp.round(phat).astype(jnp.int32) - probe // 2,
                         0, n_pad - probe)
        base = pl.multiple_of((start // LANE) * LANE, LANE)
        win = keys_ref[:, pl.ds(base, w)]
        at = base + lane
        below = (at >= start) & (at < start + probe) & (win < q)
        pos = jnp.minimum(start + jnp.sum(below.astype(jnp.int32)), count)
        return jnp.where(olane == i, pos, acc)

    out_ref[...] = jax.lax.fori_loop(
        0, QBLOCK, one, jnp.zeros((1, QBLOCK), jnp.int32))


@partial(jax.jit, static_argnames=("probe", "radix_bits", "interpret"))
def spline_search(queries, knot_keys, knot_pos, radix_table, keys_f,
                  fscal, iscal, *, probe: int, radix_bits: int,
                  interpret: bool):
    """Lower-bound positions for a batch of query keys on ONE partition.

    queries:   (Q,) f32, Q % QBLOCK == 0
    knot_keys/knot_pos: (M,) f32 ; radix_table: (R,) int32
    keys_f:    (N,) f32 sorted (sentinel-padded)
    fscal:     (2,) f32 [kmin, scale] ; iscal: (2,) int32 [n_knots, count]
    """
    nq = queries.shape[0]
    assert nq % QBLOCK == 0
    n_pad = keys_f.shape[0]
    width = cdiv(n_pad, LANE) * LANE + window_lanes(probe)
    keys = jnp.pad(keys_f, (0, width - n_pad), constant_values=3e38)
    smem = partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        partial(_kernel, probe=probe, radix_bits=radix_bits, n_pad=n_pad),
        grid=(nq // QBLOCK,),
        in_specs=[
            smem(),
            smem(),
            smem((None, 1, QBLOCK), lambda i: (i, 0, 0)),
            smem(),
            smem(),
            smem(),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, QBLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nq), jnp.int32),
        interpret=interpret,
    )(fscal, iscal, queries.reshape(-1, 1, QBLOCK), knot_keys, knot_pos,
      radix_table, keys.reshape(1, -1))
    return out.reshape(-1)
