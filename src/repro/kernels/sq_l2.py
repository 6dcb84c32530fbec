"""Pallas TPU kernel: quantized-domain scaled L2 for the HNSW-SQ baseline.

The optimized HNSW-SQ distance (paper §3.2.2 + the Qdrant "no-decode" trick)
never dequantizes either operand:

    d²(q, x) ≈ Σ_d s2_d · (q_d − x_d)²       (codes int, s2_d = (scale_d/levels)²)

Integer subtract/square runs on VPU int lanes; the per-dimension float scale
is a single fused multiply before the lane reduction.

Tiling: grid over ⌈N/block_n⌉ database rows; the query codes and the scale
vector are replicated into every tile (tiny: D ≤ 4096 ⇒ ≤ 32 KiB together).
``block_n`` is 1024 because XLA tiles a long 1-D f32 array in 1024-element
tiles on TPU and the (N,) output block must match that tiling.
Database tile (block_n=1024, D=768, int32): 3 MiB « VMEM ✓.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import round_up


def _sq_l2_kernel(q_ref, db_ref, s2_ref, out_ref):
    q = q_ref[...].astype(jnp.int32)  # (1, D)
    db = db_ref[...].astype(jnp.int32)  # (bn, D)
    s2 = s2_ref[...]  # (1, D) f32
    diff = db - q  # int lanes
    sq = (diff * diff).astype(jnp.float32)
    out_ref[...] = jnp.sum(sq * s2, axis=-1)


def sq_l2_pallas(
    q: jax.Array,
    db: jax.Array,
    s2: jax.Array,
    *,
    block_n: int = 1024,
    interpret: bool,
) -> jax.Array:
    """q (D,) int codes, db (N, D) int codes, s2 (D,) f32 -> (N,) f32."""
    n, d = db.shape
    if q.shape != (d,) or s2.shape != (d,):
        raise ValueError(f"shape mismatch q{q.shape} s2{s2.shape} db{db.shape}")
    n_pad = round_up(max(n, 1), block_n)
    d_pad = round_up(d, 128)
    qp = jnp.zeros((1, d_pad), jnp.int32).at[0, :d].set(q.astype(jnp.int32))
    dbp = jnp.zeros((n_pad, d_pad), jnp.int32).at[:n, :d].set(db.astype(jnp.int32))
    s2p = jnp.zeros((1, d_pad), jnp.float32).at[0, :d].set(s2.astype(jnp.float32))
    grid = (n_pad // block_n,)

    out = pl.pallas_call(
        _sq_l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )(qp, dbp, s2p)
    return out[:n]
