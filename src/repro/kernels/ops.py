"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy (``impl`` argument, default "auto"):

  * ``"pallas"``     — compiled Pallas (TPU target; ``interpret=False``).
  * ``"interpret"``  — Pallas with ``interpret=True`` (kernel body executed in
                       Python on CPU; used by the test suite to validate the
                       kernels in this TPU-less container).
  * ``"ref"``        — the pure-jnp oracle (also the fast path on CPU, where
                       interpret-mode Pallas would be pointlessly slow).
  * ``"auto"``       — "pallas" when a TPU backend is present, else "ref".

All wrappers are shape-polymorphic at the Python level and jit-cached per
(shape, dtype, impl).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.flash_expand import flash_expand_pallas
from repro.kernels.flash_scan import (
    flash_round_pallas,
    flash_scan_blocked_pallas,
    flash_scan_pallas,
)
from repro.kernels.l2_batch import l2_batch_pallas
from repro.kernels.sq_l2 import sq_l2_pallas

_DEFAULT_IMPL: str | None = None


def set_default_impl(impl: str | None) -> None:
    """Force a dispatch mode globally (tests/benchmarks)."""
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = impl


def resolve_impl(impl: str = "auto") -> str:
    if impl == "auto" and _DEFAULT_IMPL is not None:
        impl = _DEFAULT_IMPL
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def _trace_tick(kernel: str, impl: str) -> None:
    """Compile-event counter: called from the Python body of each jitted
    wrapper, which runs exactly once per (shape, dtype, impl) trace — the
    same trace-time side-effect idiom the serving engine uses for its
    compile counter. Gated no-op unless obs is enabled."""
    obs.tick("kernel_traces_total", kernel=kernel, impl=impl)



@functools.partial(jax.jit, static_argnames=("impl", "block_n"))
def flash_scan(
    codes: jax.Array, adt: jax.Array, *, impl: str = "auto", block_n: int = 1024
) -> jax.Array:
    """Batched ADT lookup-accumulate: codes (N, M), adt (M, K) -> (N,)."""
    impl = resolve_impl(impl)
    _trace_tick("flash_scan", impl)
    if impl == "ref":
        return ref.flash_scan_ref(codes, adt)
    return flash_scan_pallas(
        codes, adt, block_n=block_n, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl", "block_g"))
def flash_scan_blocked(
    blocks: jax.Array, adt: jax.Array, *, impl: str = "auto", block_g: int = 8
) -> jax.Array:
    """Blocked-layout ADT scan: blocks (G, M, B), adt (M, K) -> (G, B)."""
    impl = resolve_impl(impl)
    _trace_tick("flash_scan_blocked", impl)
    if impl == "ref":
        return ref.flash_scan_blocked_ref(blocks, adt)
    return flash_scan_blocked_pallas(
        blocks, adt, block_g=block_g, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl", "block_g"))
def flash_scan_batch(
    rows: jax.Array, adt: jax.Array, *, impl: str = "auto", block_g: int = 8
) -> jax.Array:
    """Neighbor-row batch ADT scan: rows (W, R, M), adt (M, K) -> (W, R).

    The multi-expansion beam's entry point: each expanded vertex contributes
    one contiguous (R, M) neighbor-code row (the §3.3.4 mirror); the W rows
    are scored in a single blocked-kernel launch. Layout-wise this is exactly
    ``flash_scan_blocked`` with G = W, B = R — the transpose to (W, M, R)
    groups codewords by subspace within each block, so one sequential load
    fetches the R codewords of a single subspace (Figure 5, lower right).
    """
    w, r, m = rows.shape
    m2, _k = adt.shape
    if m != m2:
        raise ValueError(f"rows M={m} != adt M={m2}")
    _trace_tick("flash_scan_batch", resolve_impl(impl))
    blocks = jnp.transpose(rows, (0, 2, 1))  # (W, M, R)
    return flash_scan_blocked(blocks, adt, impl=impl, block_g=block_g)


@functools.partial(jax.jit, static_argnames=("impl", "block_b"))
def flash_round(
    codes: jax.Array, adts: jax.Array, *, impl: str = "auto", block_b: int = 8
) -> jax.Array:
    """Bulk refinement-round scan: codes (B, C, M), adts (B, M, K) -> (B, C).

    The ``strategy="bulk"`` build's kernel entry point (DESIGN.md §12): one
    RNN-Descent round scores every vertex's candidate block against that
    vertex's OWN ADT, so the table is batched per row — ``flash_scan`` with
    a leading B axis on both operands. The Flash backends' ``round_dists``
    capability hook routes here.
    """
    impl = resolve_impl(impl)
    _trace_tick("flash_round", impl)
    if impl == "ref":
        return ref.flash_round_ref(codes, adts)
    return flash_round_pallas(
        codes, adts, block_b=block_b, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def flash_expand(
    nodes: jax.Array,
    adjacency: jax.Array,
    mirror: jax.Array,
    adt: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Fused beam-expansion step (DESIGN.md §10).

    nodes (W,), adjacency (n, R), mirror (n, R, ⌈M/2⌉) packed uint8 (or
    (n, R, M) int32 legacy), adt (M, K) -> (rows (W, R), sums (W, R)).
    One program per frontier vertex: scalar-prefetched in-kernel gather of
    the adjacency row and packed code row, nibble lookup against the even
    and odd ADT rows. The ``backend.expand()`` capability hook routes here.
    """
    impl = resolve_impl(impl)
    _trace_tick("flash_expand", impl)
    if impl == "ref":
        return ref.flash_expand_ref(nodes, adjacency, mirror, adt)
    return flash_expand_pallas(
        nodes, adjacency, mirror, adt, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_c"))
def l2_batch(
    x: jax.Array,
    y: jax.Array,
    *,
    impl: str = "auto",
    block_n: int = 256,
    block_c: int = 256,
) -> jax.Array:
    """Pairwise squared L2: x (N, D), y (C, D) -> (N, C) f32."""
    impl = resolve_impl(impl)
    _trace_tick("l2_batch", impl)
    if impl == "ref":
        return ref.l2_batch_ref(x, y)
    return l2_batch_pallas(
        x, y, block_n=block_n, block_c=block_c, interpret=(impl == "interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_c"))
def nearest_centroid(
    x: jax.Array,
    centroids: jax.Array,
    *,
    banned: jax.Array | None = None,
    impl: str = "auto",
    block_n: int = 256,
    block_c: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid routing: x (N, D), centroids (S, D) ->
    (route (N,) int32, d2 (N,) f32).

    The shared routing primitive behind segment assignment — the streaming
    sharded build (graph/sharded.py), ``SegmentedAnnIndex.add`` growth
    routing, and the serving router all ask the same question, so they all
    go through the same kernel dispatch (the (N, C) distance matrix is
    ``l2_batch``, Pallas-tiled on TPU, the jnp oracle on CPU). ``banned``
    is an optional (S,) bool mask of segments that must not win (quarantined
    segments in degraded deployments)."""
    impl = resolve_impl(impl)
    _trace_tick("nearest_centroid", impl)
    if impl == "ref":
        d2 = ref.l2_batch_ref(x, centroids)
    else:
        d2 = l2_batch_pallas(
            x, centroids, block_n=block_n, block_c=block_c,
            interpret=(impl == "interpret"),
        )
    if banned is not None:
        d2 = jnp.where(banned[None, :], jnp.inf, d2)
    route = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return route, jnp.take_along_axis(d2, route[:, None].astype(jnp.int32), axis=1)[:, 0]


@functools.partial(jax.jit, static_argnames=("impl", "block_n"))
def sq_l2(
    q: jax.Array,
    db: jax.Array,
    s2: jax.Array,
    *,
    impl: str = "auto",
    block_n: int = 1024,
) -> jax.Array:
    """SQ quantized-domain distance: q (D,), db (N, D), s2 (D,) -> (N,) f32."""
    impl = resolve_impl(impl)
    _trace_tick("sq_l2", impl)
    if impl == "ref":
        return ref.sq_l2_ref(q, db, s2)
    return sq_l2_pallas(q, db, s2, block_n=block_n, interpret=(impl == "interpret"))
