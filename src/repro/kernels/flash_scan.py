"""Pallas TPU kernel: ADT lookup-accumulate — the `pshufb` analogue.

The CPU Flash inner loop is: load one 128-bit register with a subspace's ADT,
shuffle it with 16 neighbor codewords, add into the running distances. On TPU
the idiomatic translation (DESIGN.md §2) is one kernel over a *blocked* code
layout (paper §3.3.4, Figure 5): a (G, M, B) array whose (g, m) rows hold B
codewords of one subspace, B on the 128-wide lane axis.

  * the (M, K) ADT is VMEM-resident (K = 16, H = 8 ⇒ 16·M entries),
  * a (block_g, M, block_b) tile of codewords is DMA'd HBM→VMEM once,
  * the 16-way table lookup is gather-free: for each codeword value k the
    tile is compared against k and selects that subspace's table column
    ``adt[:, k]`` (a lane broadcast), accumulating into an (M, B) block;
    one sublane reduction over M gives the B sums. Integer tables make every
    summation order exact, so the kernel is bit-identical to the oracle.

Three entry points share it:

  * ``flash_scan_blocked_pallas`` — the layout itself, one shared ADT;
  * ``flash_scan_pallas`` — flat (N, M) codes, seen as one block row
    (1, M, N): the transpose is free for the n-minor layout XLA gives a
    narrow (N, M) int32 array on TPU;
  * ``flash_round_pallas`` — the bulk refinement round (DESIGN.md §12): a
    (B, C, M) candidate block against B per-vertex tables, i.e. the blocked
    layout with a table per row instead of a shared one.

VMEM budget per program (round defaults, block_g=8, C=96, M=16, K=16):
  codes tile  8×16×128 lanes×4 B   =  64 KiB
  adts tile   8×16×128 lanes×4 B   =  64 KiB
  accumulator same as codes         =  64 KiB
  out         8×128×4 B             =   4 KiB              « 16 MiB VMEM ✓
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import round_up


def _scan_kernel(blocks_ref, adt_ref, out_ref, *, k: int):
    """One tile: blocks (gb, M, bb) int32, adt (gb | 1, M, K) -> out (gb, bb)."""
    codes = blocks_ref[...]
    adt = adt_ref[...]
    acc = jnp.zeros(codes.shape, adt.dtype)
    for j in range(k):  # compare-select per codeword value, lane-broadcast
        acc = acc + jnp.where(codes == j, adt[:, :, j : j + 1], 0)
    out_ref[...] = jnp.sum(acc, axis=1)


def _scan(
    blocks: jax.Array,
    adts: jax.Array,
    *,
    block_g: int,
    block_b: int,
    interpret: bool,
) -> jax.Array:
    """blocks (G, M, B) codewords, adts (1 | G, M, K) -> (G, B)."""
    g, m, b = blocks.shape
    ga, m2, k = adts.shape
    if m != m2 or ga not in (1, g):
        raise ValueError(f"codes (G={g}, M={m}) != tables (G={ga}, M={m2})")
    shared = ga == 1
    g_pad = round_up(max(g, 1), block_g)
    b_pad = round_up(max(b, 1), block_b)
    blocks_p = (
        jnp.zeros((g_pad, m, b_pad), jnp.int32)
        .at[:g, :, :b]
        .set(blocks.astype(jnp.int32))
    )
    if not shared:
        adts = jnp.zeros((g_pad, m, k), adts.dtype).at[:g].set(adts)
    out = pl.pallas_call(
        functools.partial(_scan_kernel, k=k),
        grid=(g_pad // block_g, b_pad // block_b),
        in_specs=[
            pl.BlockSpec((block_g, m, block_b), lambda i, j: (i, 0, j)),
            pl.BlockSpec(
                (1 if shared else block_g, m, k),
                (lambda i, j: (0, 0, 0)) if shared else (lambda i, j: (i, 0, 0)),
            ),
        ],
        out_specs=pl.BlockSpec((block_g, block_b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g_pad, b_pad), adts.dtype),
        interpret=interpret,
    )(blocks_p, adts)
    return out[:g, :b]


def flash_scan_pallas(
    codes: jax.Array, adt: jax.Array, *, block_n: int = 1024, interpret: bool
) -> jax.Array:
    """codes (N, M) int in [0, K); adt (M, K) int32/float32 -> (N,).

    ``block_n`` (a multiple of 128) codewords per program. ``interpret=True``
    executes the kernel body with the Pallas interpreter (CPU tests).
    """
    n, m = codes.shape
    if adt.shape[0] != m:
        raise ValueError(f"codes M={m} != adt M={adt.shape[0]}")
    return _scan(
        codes.T[None], adt[None], block_g=1, block_b=block_n, interpret=interpret
    )[0]


def flash_scan_blocked_pallas(
    blocks: jax.Array, adt: jax.Array, *, block_g: int = 8, interpret: bool
) -> jax.Array:
    """Access-aware neighbor-block scan: blocks (G, M, B) -> (G, B).

    ``B`` is the neighbor batch per "register load" (16 on 128-bit CPU SIMD,
    128 = one lane row on TPU). The (g, m) rows are contiguous in HBM — one
    sequential DMA per tile, zero random access, matching Figure 5's layout.
    """
    g, m, b = blocks.shape
    if adt.shape[0] != m:
        raise ValueError(f"blocks M={m} != adt M={adt.shape[0]}")
    return _scan(blocks, adt[None], block_g=block_g, block_b=b, interpret=interpret)


def flash_round_pallas(
    codes: jax.Array, adts: jax.Array, *, block_b: int = 8, interpret: bool
) -> jax.Array:
    """codes (B, C, M) int in [0, K); adts (B, M, K) -> (B, C).

    One RNN-Descent round scores every round vertex's C candidates against
    that vertex's OWN table (there is no shared query), so the table rides
    in the same row tile as the codes: ``block_b`` vertices per program.
    """
    b, c, m = codes.shape
    b2, m2, _k = adts.shape
    if b != b2 or m != m2:
        raise ValueError(f"codes (B={b}, M={m}) != adts (B={b2}, M={m2})")
    return _scan(
        jnp.transpose(codes, (0, 2, 1)), adts,
        block_g=block_b, block_b=c, interpret=interpret,
    )
