"""Pallas TPU kernel: tiled pairwise squared-L2 distance matrix.

The full-precision distance path (baseline HNSW) and k-means codebook
training both reduce to ``(N, D) × (C, D) → (N, C)`` squared distances. On the
MXU this is one matmul plus rank-1 norm corrections:

    d²(x, y) = ‖x‖² + ‖y‖² − 2·x·yᵀ

Tiling: 2-D grid over (⌈N/bn⌉, ⌈C/bc⌉); each program loads an x tile
(bn, D) and a y tile (bc, D) into VMEM, runs one (bn × D) @ (D × bc) MXU
matmul in float32, and writes the (bn, bc) tile. The norm terms are computed
in-kernel so HBM sees each operand exactly once per tile.

Defaults bn = bc = 256, D ≤ 2048:
  x tile 256×2048×4 = 2 MiB, y tile 2 MiB, out 256×256×4 = 256 KiB  « VMEM ✓
MXU alignment: bn/bc multiples of 128 lanes; D is zero-padded to a multiple
of 128 by the wrapper (zero pads don't change L2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import round_up


def _l2_kernel(x_ref, y_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)  # (bn, D)
    y = y_ref[...].astype(jnp.float32)  # (bc, D)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (bn, 1)
    y2 = jnp.sum(y * y, axis=-1)  # (bc,)
    xy = jax.lax.dot_general(
        x,
        y,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bc) on the MXU
    out_ref[...] = jnp.maximum(x2 + y2[None, :] - 2.0 * xy, 0.0)


def l2_batch_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    block_n: int = 256,
    block_c: int = 256,
    interpret: bool,
) -> jax.Array:
    """x (N, D), y (C, D) -> (N, C) float32 squared distances."""
    n, d = x.shape
    c, d2 = y.shape
    if d != d2:
        raise ValueError(f"dim mismatch {d} vs {d2}")
    n_pad = round_up(max(n, 1), block_n)
    c_pad = round_up(max(c, 1), block_c)
    d_pad = round_up(d, 128)
    xp = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(x.astype(jnp.float32))
    yp = jnp.zeros((c_pad, d_pad), jnp.float32).at[:c, :d].set(y.astype(jnp.float32))
    grid = (n_pad // block_n, c_pad // block_c)

    out = pl.pallas_call(
        _l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, c_pad), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:n, :c]
