"""Distance backends — the pluggable "how do we compare" axis of the paper.

Graph construction (CA + NS stages) only ever *compares* distances (paper
§2.2), so the index build is written against a small protocol and the five
methods of the paper plug in:

    fp32   unmodified HNSW          (full-precision L2)
    pq     HNSW-PQ   (§3.2.1)       ADC tables for CA, SDC tables for NS
    sq     HNSW-SQ   (§3.2.2)       int-domain scaled L2 (no-decode variant)
    pca    HNSW-PCA  (§3.2.3)       full-precision L2 on d_PCA principal dims
    flash  HNSW-Flash (§3.3)        quantized ADT (CA) + quantized SDT (NS)

Protocol (all distances are *comparison-valid within one backend* — squared
L2, or a monotone affine image of it; never mixed across backends):

    prepare_query(q_raw)        -> qctx   per-inserted-vector state
    query_dists(qctx, ids)      -> f32    distances query -> stored ids
    neighbor_dists_batch(qctx, nodes, ids) -> f32  the CA hot path: nodes
                                  (W,) graph vertices whose adjacency rows
                                  ``ids`` (W, R) are being scored (−1 =
                                  masked row). Naming the vertices lets the
                                  Flash blocked layout (§3.3.4) read W
                                  contiguous code rows through the blocked
                                  Pallas kernel (kernels.ops.flash_scan_batch)
                                  instead of W·R random gathers.
    pair_dists(ids_a, ids_b)    -> f32    distances between stored ids
    pair_table(ids)             -> f32    (C, C) all-pairs distances among
                                  stored ids (neighbour selection's
                                  occlusion table). Default: the broadcast
                                  ``pair_dists``; Flash contracts one-hot
                                  codes against the SDT on the MXU.
    supports_expand(r)          -> bool   capability hook: can ``expand``
                                  serve adjacency rows of width ``r``?
                                  (static — checked once at trace time by
                                  ``beam_search``; False everywhere except
                                  the Flash blocked layout)
    round_dists(qctxs, ids)     -> f32    the BULK-round hot path (DESIGN.md
                                  §12): qctxs a query-context pytree with
                                  leading (B,), ids (B, C) candidate blocks
                                  (callers mask invalid slots) — one
                                  refinement round of the ``strategy="bulk"``
                                  build scored in a single batched call.
                                  Default: vmapped ``query_dists`` (correct
                                  for every backend); the Flash family
                                  overrides with one blocked Pallas launch
                                  (kernels.ops.flash_round).
    supports_bulk_round()       -> bool   capability hook: does
                                  ``round_dists`` dispatch through the
                                  batched-round kernel (rather than the
                                  vmapped gather default)? Static — the
                                  CI guard (benchmarks/check_expand_guard)
                                  asserts it is claimed exactly by the
                                  backends whose hook reaches the kernel.
    expand(qctx, nodes, adjacency) -> (rows, dists)  the FUSED CA hot path
                                  (DESIGN.md §10): one whole beam-expansion
                                  step in a single kernel — scalar-prefetch
                                  the (W,) frontier, gather adjacency +
                                  packed code rows in-kernel, score via the
                                  packed-nibble ADT lookup. Returns the
                                  gathered (W, R) rows and their (W, R) f32
                                  distances (callers mask invalid slots).
    with_updated_edges(ids, nbr_ids) -> backend   commit hook (blocked layout)
    extend(new_vectors)         -> backend  dynamic growth (DESIGN.md §8):
                                  encode new raw vectors with the FROZEN
                                  coder and append their codes (and, for the
                                  blocked layout, empty mirror rows) — the
                                  hook ``repro.index.AnnIndex.add`` uses to
                                  grow an index without refitting anything.
    raw_dists(q_raw, ids)       -> f32    EXACT squared L2 from the raw query
                                  to stored ids — the rerank-stage hook
                                  (DESIGN.md §11). Served from the retained
                                  raw-vector table (``keep_raw=True`` builds;
                                  fp32 stores raw by definition); raises for
                                  compact backends built without one.
    recon_vectors(ids)          -> f32    coder-reconstructed (decoded)
                                  vectors for stored ids — the approximate
                                  rerank source for deployments that do NOT
                                  retain raw vectors (zero extra resident
                                  bytes; see graph.rerank.ReconstructReranker).
    state_dict()                -> dict[str, np.ndarray]  full serializable
                                  state (codes + coder params, nested keys
                                  dotted); ``from_state(state)`` rebuilds the
                                  backend bit-exactly — the snapshot hooks
                                  ``repro.serve`` persists an index through
                                  (DESIGN.md §9). The optional ``raw`` table
                                  is included iff retained (snapshot format
                                  v3); absent keys restore to None, which is
                                  how v1/v2 snapshots migrate.

Backends are registered pytrees so whole index builds jit/vmap/shard cleanly.
"""

from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro import core, obs
from repro.kernels import ops


def _flatten_state(prefix: str, val, out: dict) -> None:
    """Recursively flatten a backend field into dotted-key numpy arrays.

    Coders are NamedTuple pytrees of arrays (possibly nested, e.g.
    ``SQCoder.params``), so structure is encoded purely in the key path."""
    if isinstance(val, tuple) and hasattr(val, "_fields"):
        for f in val._fields:
            _flatten_state(f"{prefix}.{f}", getattr(val, f), out)
    else:
        out[prefix] = np.asarray(val)


def _unflatten_state(prefix: str, state, nt_cls):
    """Inverse of :func:`_flatten_state`; ``nt_cls`` names the NamedTuple
    class to rebuild (None = plain array leaf). Nested NamedTuple fields are
    discovered through resolved type hints."""
    if nt_cls is None:
        if prefix not in state:
            raise KeyError(f"backend state missing array {prefix!r}")
        return jnp.asarray(state[prefix])
    hints = typing.get_type_hints(nt_cls)
    vals = []
    for f in nt_cls._fields:
        hint = hints.get(f)
        sub = hint if isinstance(hint, type) and hasattr(hint, "_fields") else None
        vals.append(_unflatten_state(f"{prefix}.{f}", state, sub))
    return nt_cls(*vals)


def _l2(a: jax.Array, b: jax.Array) -> jax.Array:
    d = a - b
    return jnp.sum(d * d, axis=-1)


def _grow_raw(raw, new):
    """extend() helper: grow the optional retained-raw table in lockstep."""
    return None if raw is None else jnp.concatenate([raw, new])


class _Base:
    """Shared default implementations."""

    #: structured (NamedTuple coder) fields: name -> class; everything else
    #: in ``_fields`` is a plain array. Subclasses override as needed.
    _coder_fields: dict = {}
    #: fields that may be None (skipped by state_dict, restored as None when
    #: absent — the v1/v2 → v3 snapshot migration path).
    _optional_fields: tuple = ("raw",)

    @property
    def has_raw(self) -> bool:
        """Whether this backend retains raw vectors for exact rerank."""
        return getattr(self, "raw", None) is not None

    def raw_dists(self, q_raw, ids):
        """Exact squared L2 from the raw query to stored ids (rerank hook,
        DESIGN.md §11); requires a retained raw table (``keep_raw=True``)."""
        raw = getattr(self, "raw", None)
        if raw is None:
            raise ValueError(
                f"{type(self).__name__} retains no raw vectors; build with "
                "keep_raw=True (or rerank through an external raw table, "
                "e.g. graph.rerank.RawVectors)"
            )
        return _l2(raw[ids], q_raw)

    def recon_vectors(self, ids):
        raise NotImplementedError(
            f"{type(self).__name__} has no coder-reconstruction path "
            "(recon_vectors); use exact rerank instead"
        )

    def pair_table(self, ids):
        """(C,) ids -> (C, C) pair distances (selection's occlusion table)."""
        return self.pair_dists(ids[:, None], ids[None, :])

    def neighbor_dists_batch(self, qctx, nodes, ids):  # noqa: ARG002
        # Default: one batched gather-and-score; every backend's query_dists
        # broadcasts over leading axes, so (W, R) ids come back as (W, R).
        return self.query_dists(qctx, ids)

    def supports_expand(self, r: int) -> bool:  # noqa: ARG002
        """Fused-expansion capability (DESIGN.md §10): default unsupported."""
        return False

    def round_dists(self, qctxs, ids):
        """Bulk-round scoring (DESIGN.md §12): qctxs pytree with leading
        (B,), ids (B, C) -> (B, C) f32. Default: one vmapped gather-and-
        score — semantically the ground truth the kernel path must match."""
        return jax.vmap(self.query_dists)(qctxs, ids)

    def supports_bulk_round(self) -> bool:
        """Batched-round kernel capability: default False (``round_dists``
        falls back to the vmapped gather, which is always available)."""
        return False

    def expand(self, qctx, nodes, adjacency):
        raise NotImplementedError(
            f"{type(self).__name__} has no fused expand() path; beam_search "
            "must take the gather+scan fallback (supports_expand() is False)"
        )

    def with_updated_edges(self, ids, nbr_ids):  # noqa: ARG002
        return self

    def extend(self, new_vectors):
        raise NotImplementedError(
            f"{type(self).__name__} does not support dynamic growth"
        )

    def state_dict(self) -> dict:
        """Full serializable state: flat ``{dotted_key: np.ndarray}``.

        Covers codes AND fitted coder parameters, so
        ``type(b).from_state(b.state_dict())`` reproduces identical
        distances (the ``repro.serve`` snapshot contract)."""
        out: dict = {}
        for name in self._fields:
            val = getattr(self, name)
            if val is None and name in self._optional_fields:
                continue
            _flatten_state(name, val, out)
        return out

    @classmethod
    def from_state(cls, state) -> "_Base":
        """Rebuild a backend from :meth:`state_dict` output (bit-exact).

        Optional fields absent from ``state`` (e.g. ``raw`` in pre-v3
        snapshots, or any build without ``keep_raw``) restore as None."""
        vals = []
        for name in cls._fields:
            present = name in state or any(
                k.startswith(name + ".") for k in state
            )
            if not present and name in cls._optional_fields:
                vals.append(None)
                continue
            vals.append(_unflatten_state(name, state, cls._coder_fields.get(name)))
        return cls(*vals)

    def tree_flatten(self):
        children = tuple(getattr(self, name) for name in self._fields)
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):  # noqa: ARG003
        obj = cls.__new__(cls)
        for name, child in zip(cls._fields, children):
            object.__setattr__(obj, name, child)
        return obj


@jax.tree_util.register_pytree_node_class
class FP32Backend(_Base):
    """Unmodified HNSW: exact squared L2 on raw vectors."""

    _fields = ("vectors",)

    def __init__(self, vectors: jax.Array):
        self.vectors = vectors

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    def prepare_query(self, q: jax.Array):
        return q

    def query_dists(self, qctx, ids):
        return _l2(self.vectors[ids], qctx)

    def pair_dists(self, ids_a, ids_b):
        ids_a, ids_b = jnp.broadcast_arrays(ids_a, ids_b)
        return _l2(self.vectors[ids_a], self.vectors[ids_b])

    @property
    def has_raw(self) -> bool:
        return True  # the stored vectors ARE raw

    def raw_dists(self, q_raw, ids):
        return _l2(self.vectors[ids], q_raw)

    def recon_vectors(self, ids):
        return self.vectors[ids]  # lossless "reconstruction"

    def extend(self, new_vectors):
        new = jnp.asarray(new_vectors, jnp.float32)
        return FP32Backend(jnp.concatenate([self.vectors, new]))


@jax.tree_util.register_pytree_node_class
class PCABackend(_Base):
    """HNSW-PCA: exact L2 on the first d_PCA principal components."""

    _fields = ("coder", "z", "raw")
    _coder_fields = {"coder": core.PCACoder}

    def __init__(self, coder: core.PCACoder, z: jax.Array, raw=None):
        self.coder = coder
        self.z = z  # (n, d) projected database
        self.raw = raw  # optional (n, D) raw table (keep_raw=True)

    @property
    def n(self) -> int:
        return self.z.shape[0]

    def prepare_query(self, q: jax.Array):
        return core.pca_encode(self.coder, q[None, :])[0]

    def query_dists(self, qctx, ids):
        return _l2(self.z[ids], qctx)

    def pair_dists(self, ids_a, ids_b):
        ids_a, ids_b = jnp.broadcast_arrays(ids_a, ids_b)
        return _l2(self.z[ids_a], self.z[ids_b])

    def recon_vectors(self, ids):
        return self.z[ids] @ self.coder.rot.T + self.coder.mean

    def extend(self, new_vectors):
        new = jnp.asarray(new_vectors, jnp.float32)
        z_new = core.pca_encode(self.coder, new)
        return PCABackend(
            self.coder, jnp.concatenate([self.z, z_new]), _grow_raw(self.raw, new)
        )


@jax.tree_util.register_pytree_node_class
class SQBackend(_Base):
    """HNSW-SQ: quantized-domain scaled L2, no decode of either operand."""

    _fields = ("coder", "codes", "raw")
    _coder_fields = {"coder": core.SQCoder}

    def __init__(self, coder: core.SQCoder, codes: jax.Array, raw=None):
        self.coder = coder
        self.codes = codes  # (n, D) int32 levels
        self.raw = raw  # optional (n, D) raw table (keep_raw=True)

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    def prepare_query(self, q: jax.Array):
        return core.sq_encode(self.coder, q[None, :])[0]

    def query_dists(self, qctx, ids):
        return core.sq_dist(self.coder, qctx, self.codes[ids])

    def pair_dists(self, ids_a, ids_b):
        ids_a, ids_b = jnp.broadcast_arrays(ids_a, ids_b)
        return core.sq_dist(self.coder, self.codes[ids_a], self.codes[ids_b])

    def recon_vectors(self, ids):
        return core.sq_decode(self.coder.params, self.codes[ids])

    def extend(self, new_vectors):
        new = jnp.asarray(new_vectors, jnp.float32)
        codes_new = core.sq_encode(self.coder, new)
        return SQBackend(
            self.coder,
            jnp.concatenate([self.codes, codes_new]),
            _grow_raw(self.raw, new),
        )


@jax.tree_util.register_pytree_node_class
class PQBackend(_Base):
    """HNSW-PQ: float ADC table per query (CA), SDC centroid tables (NS)."""

    _fields = ("coder", "codes", "raw")
    _coder_fields = {"coder": core.PQCoder}

    def __init__(self, coder: core.PQCoder, codes: jax.Array, raw=None):
        self.coder = coder
        self.codes = codes  # (n, M) int32
        self.raw = raw  # optional (n, D) raw table (keep_raw=True)

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    def prepare_query(self, q: jax.Array):
        return core.pq_adc_table(self.coder, q)  # (M, K) f32

    def query_dists(self, qctx, ids):
        return core.adc_lookup(qctx, self.codes[ids]).astype(jnp.float32)

    def pair_dists(self, ids_a, ids_b):
        return core.pq_sdc_lookup(
            self.coder, self.codes[ids_a], self.codes[ids_b]
        ).astype(jnp.float32)

    def recon_vectors(self, ids):
        cb = self.coder.codebooks  # (M, K, ds)
        gathered = cb[jnp.arange(self.coder.m), self.codes[ids]]  # (..., M, ds)
        return gathered.reshape(*gathered.shape[:-2], -1)  # caller unpads

    def extend(self, new_vectors):
        new = jnp.asarray(new_vectors, jnp.float32)
        codes_new = core.pq_encode(self.coder, new)
        return PQBackend(
            self.coder,
            jnp.concatenate([self.codes, codes_new]),
            _grow_raw(self.raw, new),
        )


@jax.tree_util.register_pytree_node_class
class FlashBackend(_Base):
    """HNSW-Flash: quantized register-resident ADT + shared quantized SDT.

    ADT sums (CA stage) and SDT sums (NS stage) share one (dist_min, Δ, H)
    quantizer (paper §3.3.3) so they are mutually comparable — required
    because neighbor selection compares δ(u, v) [SDT] with δ(v, x) [ADT].
    """

    _fields = ("coder", "codes", "raw")
    _coder_fields = {"coder": core.FlashCoder}

    def __init__(self, coder: core.FlashCoder, codes: jax.Array, raw=None):
        self.coder = coder
        self.codes = codes  # (n, M) int32 in [0, K)
        self.raw = raw  # optional (n, D) raw table (keep_raw=True)

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    def prepare_query(self, q: jax.Array):
        return core.query_ctx(self.coder, q)

    def query_dists(self, qctx, ids):
        return core.adc_lookup(qctx.adt_q, self.codes[ids]).astype(jnp.float32)

    def pair_dists(self, ids_a, ids_b):
        return core.sdc_lookup(
            self.coder, self.codes[ids_a], self.codes[ids_b]
        ).astype(jnp.float32)

    def pair_table(self, ids):
        return core.sdc_table(self.coder, self.codes[ids])

    def round_dists(self, qctxs, ids):
        """One blocked kernel launch per bulk round (DESIGN.md §12): gather
        the candidates' code rows, contract against the per-vertex ADTs.
        Integer tables → bit-exact with the vmapped ``query_dists`` default
        (one-hot select-sum == table gather-sum on the same int32 levels)."""
        return ops.flash_round(self.codes[ids], qctxs.adt_q).astype(jnp.float32)

    def supports_bulk_round(self) -> bool:
        return True

    def recon_vectors(self, ids):
        cb = self.coder.codebooks  # (M, K, ds)
        gathered = cb[jnp.arange(self.coder.m_f), self.codes[ids]]  # (..., M, ds)
        z_hat = gathered.reshape(*gathered.shape[:-2], -1)[..., : self.coder.d_f]
        return z_hat @ self.coder.rot.T + self.coder.mean

    def extend(self, new_vectors):
        new = jnp.asarray(new_vectors, jnp.float32)
        codes_new = core.encode(self.coder, new)
        return FlashBackend(
            self.coder,
            jnp.concatenate([self.codes, codes_new]),
            _grow_raw(self.raw, new),
        )


@jax.tree_util.register_pytree_node_class
class FlashBlockedBackend(FlashBackend):
    """Flash + the access-aware neighbor layout of §3.3.4, 4-bit packed.

    In addition to per-node codes, maintains ``nbr_codes`` — each vertex's
    neighbors' codewords stored contiguously with the vertex, so the CA hot
    loop reads one sequential row (one HBM→VMEM DMA) instead of R random
    gathers. For the paper's Flash configuration (K ≤ 16, L_F ≤ 4) the
    mirror is **packed**: (n, R, ⌈M/2⌉) uint8, two codewords per int8 lane
    exactly as the CPU implementation stores them — half the HBM footprint
    and DMA bytes of the former (n, R, M) int32 layout, with unpack fused
    into the kernels that read it. K > 16 coders (PQ-style tables) keep the
    unpacked int32 mirror. ``with_updated_edges`` is the commit hook that
    keeps the mirror in sync — the memory-for-locality trade the paper
    measures in its index-size figures (Figure 7).

    This backend owns the fused ``expand()`` path (DESIGN.md §10): one
    Pallas program per beam-expansion step, with the adjacency-row and
    code-row gathers done in-kernel via scalar prefetch and the ADT lookup
    fused behind them (`kernels.ops.flash_expand`).
    """

    _fields = ("coder", "codes", "nbr_codes", "raw")
    _coder_fields = {"coder": core.FlashCoder}

    def __init__(
        self, coder: core.FlashCoder, codes: jax.Array, nbr_codes: jax.Array,
        raw=None,
    ):
        super().__init__(coder, codes, raw)
        # (n, R, ⌈M/2⌉) uint8 packed (K ≤ 16) | (n, R, M) int32 legacy;
        # code 0 where id == -1.
        self.nbr_codes = nbr_codes

    @property
    def mirror_packed(self) -> bool:
        return self.nbr_codes.dtype == jnp.uint8

    def _mirror_rows_unpacked(self, nodes):
        """Gather (…, R, M) int32 codewords for ``nodes``'s mirror rows."""
        rows = self.nbr_codes[jnp.maximum(nodes, 0)]
        if self.mirror_packed:
            return core.unpack_codes(rows, self.coder.m_f)
        return rows

    def supports_expand(self, r: int) -> bool:
        """Fused path serves exactly the mirror's layer width (the base
        layer, where ~all CA traffic happens)."""
        return r == self.nbr_codes.shape[1]

    def expand(self, qctx, nodes, adjacency):
        """One fused beam-expansion step: in-kernel gather of the W frontier
        vertices' adjacency + packed code rows, packed ADT lookup
        (kernels.ops.flash_expand). Bit-exact with the gather+scan fallback:
        integer table sums are exact in any order."""
        rows, sums = ops.flash_expand(
            nodes, adjacency, self.nbr_codes, qctx.adt_q
        )
        return rows, sums.astype(jnp.float32)

    def neighbor_dists_batch(self, qctx, nodes, ids):
        """Multi-expansion CA block: W contiguous mirror rows, scored through
        the blocked Pallas kernel (§3.3.4 restated for W rows — one
        HBM→VMEM DMA per expanded vertex, zero per-neighbor gathers). The
        unfused fallback to :meth:`expand`, kept for parity testing and for
        callers that already hold the gathered rows.

        Static shape dispatch: the mirror tracks one layer's degree (the
        base layer); other widths fall back to the gather path.
        """
        if ids.shape[-1] != self.nbr_codes.shape[1]:
            return self.query_dists(qctx, ids)
        rows = self._mirror_rows_unpacked(nodes)  # (W, R, M)
        return ops.flash_scan_batch(rows, qctx.adt_q).astype(jnp.float32)

    def with_updated_edges(self, ids, nbr_ids):
        """ids (...,) vertices whose lists changed (out-of-bounds = dropped);
        nbr_ids (..., R) their new neighbor lists."""
        if nbr_ids.shape[-1] != self.nbr_codes.shape[1]:
            return self  # non-base-layer commit: mirror not affected
        # pack each vertex's codes once, then gather bytes: gathering int32
        # code rows first would build an (..., R, M) int32 block — 24 GB
        # in TPU tiling for a whole-layer commit at n = 10⁶
        table = (
            core.pack_codes(self.codes) if self.mirror_packed else self.codes
        )
        rows = jnp.where(
            (nbr_ids >= 0)[..., None], table[jnp.maximum(nbr_ids, 0)], 0
        )  # (..., R, ⌈M/2⌉) | (..., R, M)
        nbr_codes = self.nbr_codes.at[ids].set(rows, mode="drop")
        return FlashBlockedBackend(self.coder, self.codes, nbr_codes, self.raw)

    def extend(self, new_vectors):
        """Append codes for the new vectors plus all-empty mirror rows; the
        rows fill in as the growing build commits edges through
        ``with_updated_edges``."""
        new = jnp.asarray(new_vectors, jnp.float32)
        codes_new = core.encode(self.coder, new)
        mirror_new = jnp.zeros(
            (new.shape[0],) + self.nbr_codes.shape[1:], self.nbr_codes.dtype
        )
        return FlashBlockedBackend(
            self.coder,
            jnp.concatenate([self.codes, codes_new]),
            jnp.concatenate([self.nbr_codes, mirror_new]),
            _grow_raw(self.raw, new),
        )

    @classmethod
    def from_state(cls, state) -> "FlashBlockedBackend":
        """Rebuild from :meth:`state_dict` output, migrating the legacy
        unpacked (n, R, M) int32 mirror (snapshot format_version 1) to the
        packed layout when the coder's K fits 4 bits — distances are
        unchanged (pack∘unpack is the identity on codes < 16)."""
        be = super().from_state(state)
        if not be.mirror_packed and be.coder.k <= 16:
            be = FlashBlockedBackend(
                be.coder, be.codes, core.pack_codes(be.nbr_codes), be.raw
            )
        return be


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

#: Valid ``make_backend`` kinds, in paper order. The ``repro.index`` facade
#: registry validates against this same tuple (see :func:`kinds`).
KINDS = ("fp32", "pq", "sq", "pca", "flash", "flash_blocked")

#: Backend classes by class name — what ``repro.serve`` snapshot manifests
#: record, so ``load`` can route state back to the right ``from_state``.
CLASSES: dict[str, type] = {
    c.__name__: c
    for c in (
        FP32Backend, PCABackend, SQBackend, PQBackend,
        FlashBackend, FlashBlockedBackend,
    )
}


def kinds() -> tuple[str, ...]:
    """The backend kinds :func:`make_backend` accepts."""
    return KINDS


def make_backend(
    kind: str,
    data: jax.Array,
    key: jax.Array | None = None,
    *,
    r_for_blocked: int | None = None,
    keep_raw: bool = False,
    **coder_kwargs,
):
    """Fit a coder on ``data`` and wrap it with its backend.

    kind ∈ :func:`kinds`. ``coder_kwargs`` are forwarded to the fitter
    (e.g. d_f/m_f for flash, m/l_pq for pq…); fp32 stores raw vectors and
    takes none. ``keep_raw=True`` additionally retains ``data`` on the
    backend (4·n·D bytes) to serve the exact rerank stage without an
    external table (DESIGN.md §11); it flows through ``extend()`` and
    ``state_dict()``, so grown and snapshotted indexes keep it. fp32 is
    its own raw table, so the flag is a no-op there.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    data = jnp.asarray(data, jnp.float32)
    raw = data if keep_raw else None
    if kind == "fp32":
        if coder_kwargs:
            raise ValueError(
                "fp32 stores raw vectors and takes no coder options; got "
                f"{sorted(coder_kwargs)} (did you mean another kind of "
                f"{', '.join(KINDS)}?)"
            )
        return FP32Backend(data)
    if kind == "pca":
        coder = core.fit_pca_coder(data, **coder_kwargs)
        return PCABackend(coder, core.pca_encode(coder, data), raw)
    if kind == "sq":
        coder = core.fit_sq(data, **coder_kwargs)
        return SQBackend(coder, core.sq_encode(coder, data), raw)
    if kind == "pq":
        coder = core.fit_pq(key, data, **coder_kwargs)
        return PQBackend(coder, core.pq_encode(coder, data), raw)
    if kind in ("flash", "flash_blocked"):
        coder = core.fit_flash(key, data, **coder_kwargs)
        with obs.span("build/coder/encode"):
            codes = core.encode(coder, data)
            if kind == "flash":
                return FlashBackend(coder, codes, raw)
            if r_for_blocked is None:
                raise ValueError(
                    "flash_blocked needs r_for_blocked (max neighbors)"
                )
            if coder.k <= 16:  # 4-bit codes: packed mirror (two per byte)
                nbr_codes = jnp.zeros(
                    (data.shape[0], r_for_blocked, (coder.m_f + 1) // 2),
                    jnp.uint8,
                )
            else:  # K > 16 (PQ-style tables): unpacked legacy layout
                nbr_codes = jnp.zeros(
                    (data.shape[0], r_for_blocked, coder.m_f), jnp.int32
                )
            return FlashBlockedBackend(coder, codes, nbr_codes, raw)
    raise ValueError(
        f"unknown backend kind {kind!r}; valid kinds: {', '.join(KINDS)}"
    )
