"""Scalar quantization + distance-table quantization (paper §3.2.2, Eq. 9).

Two uses in the paper:

1. **HNSW-SQ baseline**: per-dimension scalar quantization of raw vectors to
   ``L_SQ``-bit integers (8 by default), distances computed in the quantized
   domain with a per-dimension scale.
2. **Flash ADT/SDT compression**: every partial distance in the asymmetric /
   symmetric tables is mapped to an ``H``-bit level with a *shared*
   ``(dist_min, Δ)`` so ADT and SDT values stay mutually comparable (§3.3.3):

       η(dist) = floor((dist − dist_min) / Δ · (2^H − 1))

   Since the same affine map is applied to every subspace, the *sum* over
   subspaces is a monotone affine image of the true sum (up to rounding), which
   is all a comparison-only consumer needs (Lemma 1 / Theorem 1).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SQParams(NamedTuple):
    """Per-dimension scalar-quantization parameters.

    lo:    (D,) per-dim minimum.
    scale: (D,) per-dim (hi - lo), clamped away from zero.
    bits:  () int32 — number of bits per dimension.
    """

    lo: jax.Array
    scale: jax.Array
    bits: jax.Array


def sq_fit(x: jax.Array, *, bits: int = 8) -> SQParams:
    """Fit per-dimension ranges on (a sample of) the dataset."""
    lo = jnp.min(x, axis=0)
    hi = jnp.max(x, axis=0)
    scale = jnp.maximum(hi - lo, 1e-12)
    return SQParams(lo=lo, scale=scale, bits=jnp.asarray(bits, jnp.int32))


def sq_levels(bits) -> jax.Array:
    return (1 << bits) - 1 if isinstance(bits, int) else (2**bits - 1)


def sq_encode(params: SQParams, x: jax.Array) -> jax.Array:
    """Encode float vectors to integer codes in [0, 2^bits)."""
    levels = (2 ** params.bits - 1).astype(jnp.float32)
    q = jnp.round((x - params.lo) / params.scale * levels)
    return jnp.clip(q, 0, levels).astype(jnp.int32)


def sq_decode(params: SQParams, codes: jax.Array) -> jax.Array:
    """Decode integer codes back to (lossy) floats."""
    levels = (2 ** params.bits - 1).astype(jnp.float32)
    return params.lo + codes.astype(jnp.float32) / levels * params.scale


def sq_dim_scales(params: SQParams) -> jax.Array:
    """Per-dimension squared scale factors for quantized-domain L2.

    With codes q, c:  δ²(x, y) ≈ Σ_d s2_d · (q_d − c_d)²   where
    s2_d = (scale_d / levels)². Precomputing s2 keeps the inner loop in
    integer subtract/multiply — the "no-decode" trick from the Qdrant report
    the paper cites for its optimized HNSW-SQ baseline.
    """
    levels = (2 ** params.bits - 1).astype(jnp.float32)
    return jnp.square(params.scale / levels)


class TableQuant(NamedTuple):
    """Shared affine quantizer for ADT/SDT entries (Eq. 9)."""

    dist_min: jax.Array  # ()
    delta: jax.Array  # () == dist_max - dist_min, clamped > 0
    h: jax.Array  # () bits per quantized distance


def fit_table_quant(
    per_subspace_min: jax.Array, per_subspace_max: jax.Array, *, h: int = 8
) -> TableQuant:
    """Paper §3.3.3's shared scale: dist_min = min_i dist_min_i and
    dist_max = max_i dist_max_i — every subspace's partials fit the range.

    The paper sums the per-subspace maxima so that a *sum* of quantized
    partials cannot overflow the narrow SIMD lanes it accumulates in. The
    lookups here accumulate in int32, which no sum of M H-bit levels can
    overflow, so the range only has to hold one partial: M times finer
    levels, which near neighbours of a large collection need to be told
    apart (DESIGN.md §2, A3).
    """
    dist_max = jnp.max(per_subspace_max)
    dist_min = jnp.min(per_subspace_min)
    delta = jnp.maximum(dist_max - dist_min, 1e-12)
    return TableQuant(dist_min=dist_min, delta=delta, h=jnp.asarray(h, jnp.int32))


def quantize_table(tq: TableQuant, table: jax.Array) -> jax.Array:
    """Apply Eq. 9 to a table of float partial distances -> int32 levels."""
    levels = (2 ** tq.h - 1).astype(jnp.float32)
    q = jnp.floor((table - tq.dist_min) / tq.delta * levels)
    return jnp.clip(q, 0, levels).astype(jnp.int32)


def dequantize_table(tq: TableQuant, q: jax.Array) -> jax.Array:
    """Approximate inverse of Eq. 9 (midpoint estimate)."""
    levels = (2 ** tq.h - 1).astype(jnp.float32)
    return tq.dist_min + (q.astype(jnp.float32) + 0.5) / levels * tq.delta


def pack4(codes: jax.Array) -> jax.Array:
    """Pack 4-bit codes (…, M) int32 in [0,16) into (…, M//2) uint8.

    HBM-side storage format (two codewords per byte, as on CPU); unpacked into
    int8 lanes on VMEM load because the TPU VPU has no sub-byte lanes.
    """
    if codes.shape[-1] % 2:
        raise ValueError("pack4 needs an even number of 4-bit codes")
    lo = codes[..., 0::2].astype(jnp.uint8)
    hi = codes[..., 1::2].astype(jnp.uint8)
    return lo | (hi << 4)


def unpack4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack4` -> (…, 2*Mp) int32 in [0,16)."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = ((packed >> 4) & 0xF).astype(jnp.int32)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)
