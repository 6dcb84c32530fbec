"""Pallas TPU kernel: one fused beam-expansion step (DESIGN.md §10).

The CA hot loop (`beam_search` body) otherwise runs three HLO stages per
iteration — adjacency-row gather, neighbor-code-block gather, then the
blocked ADT scan — materializing a (W·R, M) int32 code block in HBM between
each stage. This kernel performs the whole step inside one Pallas program
per frontier vertex:

  * the W frontier ids are **scalar-prefetched**; the grid is (W,) and each
    program's BlockSpec index maps pick the HBM tiles that hold vertex
    ``nodes[i]`` — the gathers become per-program HBM→VMEM DMAs chosen
    *before* the program body runs (no gather HLO, no code block in HBM),
  * the kernel reads the arrays in the layout XLA keeps them in on TPU: a
    narrow (n, R) int32 adjacency and the (n, R, ⌈M/2⌉) uint8 mirror are
    stored n-minor (vertex id on the 128-wide lane axis), so the kernel is
    handed the free transposed views (R, n) and (R, ⌈M/2⌉, n) and DMAs the
    128-vertex lane tile that holds the vertex; one lane select inside the
    kernel extracts its column. A row-shaped block of the (n, R) array
    would force XLA to relayout the whole adjacency and mirror on every
    call,
  * the mirror arrives as **packed 4-bit codes** (two codewords per byte,
    the paper's CPU storage format); the low and high nibbles are looked up
    against the even and odd ADT rows, so nibbles are never re-interleaved,
  * the lookup is the same gather-free compare-select as ``flash_scan``,
    reduced over K and then over the subspaces. Integer tables make every
    summation order exact, so the result is bit-identical to the oracle.

Visited/banned masking stays **outside** the kernel on the (W, R) output
block (see `graph/beam.py`): the visited bitmap is a (n,) scatter target
that must also be *updated* with this iteration's frontier — a sequential
read-modify-write the kernel cannot own without aliasing the bitmap — and
the tombstone mask is by design a post-search filter (banned vertices stay
traversable).

VMEM per program (R=32, M=16, K=16, packed; inputs double-buffered):
  adjacency tile  32×128×4 B                  =  16 KiB
  mirror tile     32×8×128 B (int8 tiling ×4) = 128 KiB
  tables          2 × 8×16                    (one vreg tile each)
  out rows+sums   2 × 32×1                    (one lane column each)  « 16 MiB ✓
Each program moves 48 KiB of HBM for the 384 B it uses: the price of the
n-minor layout (DESIGN.md §10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _lookup(codes, table):
    """codes (R, P, 1) int32 in [0, K); table (P, K) -> (R, 1, 1) sums."""
    kk = jax.lax.broadcasted_iota(jnp.int32, (1, 1, table.shape[-1]), 2)
    vals = jnp.where(codes == kk, table[None], 0)  # (R, P, K)
    return jnp.sum(jnp.sum(vals, axis=2, keepdims=True), axis=1, keepdims=True)


def _flash_expand_kernel(
    nodes_ref, adj_ref, mir_ref, *rest, lanes: int, packed: bool
):
    """One frontier vertex: adjacency tile (R, lanes), mirror tile
    (R, P, lanes); the vertex is lane ``nodes[i] % lanes`` of both."""
    *tables, rows_out, sums_out = rest
    lane = jnp.maximum(nodes_ref[pl.program_id(0)], 0) % lanes
    adj = adj_ref[...]
    hit = jax.lax.broadcasted_iota(jnp.int32, adj.shape, 1) == lane
    rows_out[0] = jnp.sum(jnp.where(hit, adj, 0), axis=1, keepdims=True)
    mir = mir_ref[...].astype(jnp.int32)
    hit = jax.lax.broadcasted_iota(jnp.int32, mir.shape, 2) == lane
    col = jnp.sum(jnp.where(hit, mir, 0), axis=2, keepdims=True)  # (R, P, 1)
    if packed:
        lo, hi = tables
        sums = _lookup(col & 0xF, lo[...]) + _lookup(col >> 4, hi[...])
    else:
        sums = _lookup(col, tables[0][...])
    sums_out[0] = sums


def flash_expand_pallas(
    nodes: jax.Array,
    adjacency: jax.Array,
    mirror: jax.Array,
    adt: jax.Array,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused beam-expansion step: in-kernel gather + packed lookup.

    nodes      (W,) int32 frontier vertex ids (−1 = inactive slot; clamped
               to row 0, masked by the caller exactly like the gather path).
    adjacency  (n, R) int32 neighbor lists (−1 = empty slot).
    mirror     (n, R, ⌈M/2⌉) uint8 packed codes (two per byte), or
               (n, R, M) int32 unpacked (legacy layout, K > 16 coders).
    adt        (M, K) int32/float32 quantized ADT.

    Returns (rows (W, R) int32, sums (W, R) adt.dtype): the gathered
    adjacency rows and every slot's summed partial distances. Inactive /
    empty slots carry clamped-row values — the caller masks them, bit-exactly
    matching the unfused gather+scan path. ``interpret=True`` executes the
    kernel body with the Pallas interpreter (CPU tests).
    """
    w = nodes.shape[0]
    n, r = adjacency.shape
    m, k = adt.shape
    packed = mirror.dtype == jnp.uint8
    p = mirror.shape[-1]
    expect = (m + 1) // 2 if packed else m
    if mirror.shape[0] != n or p != expect:
        raise ValueError(
            f"mirror {mirror.shape} {mirror.dtype} does not match adjacency "
            f"n={n} / adt M={m} (expected last dim {expect})"
        )
    if packed:  # low nibble = even subspace, high nibble = odd (pad row 0)
        t = jnp.zeros((2 * p, k), adt.dtype).at[:m].set(adt)
        tables = [t[0::2], t[1::2]]
    else:
        tables = [adt]
    lanes = min(_LANES, n)

    def tile(i, nref):
        return jnp.maximum(nref[i], 0) // lanes

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((r, lanes), lambda i, nref: (0, tile(i, nref))),
            pl.BlockSpec((r, p, lanes), lambda i, nref: (0, 0, tile(i, nref))),
        ]
        + [pl.BlockSpec(t.shape, lambda i, nref: (0, 0)) for t in tables],
        out_specs=[
            pl.BlockSpec((1, r, 1), lambda i, nref: (i, 0, 0)),
            pl.BlockSpec((1, r, 1, 1), lambda i, nref: (i, 0, 0, 0)),
        ],
    )
    rows, sums = pl.pallas_call(
        functools.partial(_flash_expand_kernel, lanes=lanes, packed=packed),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((w, r, 1), jnp.int32),
            jax.ShapeDtypeStruct((w, r, 1, 1), adt.dtype),
        ],
        interpret=interpret,
    )(nodes.astype(jnp.int32), adjacency.T, jnp.transpose(mirror, (1, 2, 0)), *tables)
    return rows.reshape(w, r), sums.reshape(w, r)
