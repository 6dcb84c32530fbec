"""Segment-parallel index build & search (paper §2.1.4 / §4.4, DESIGN §5).

Production vector databases shard datasets into segments of tens of millions
of vectors and build per-segment indexes concurrently; queries fan out and an
inter-shard coordinator merges top-k. The paper's technique accelerates each
segment's build and is "directly integrable into existing distributed
systems" — this module is that integration for a JAX mesh:

  * the coder (PCA + codebooks + SDT) is fitted ONCE on a host-side sample
    and broadcast — an offline training job, shared by all segments,
  * ``shard_map`` over the ("pod", "data") axes gives every device its own
    segment; each encodes its shard and runs the same jitted HNSW build —
    zero inter-device traffic during construction (embarrassingly parallel,
    matching Figure 11's linear segment scaling),
  * search: local beam search per segment, then a two-stage top-k merge —
    local top-k, ``all_gather`` along the segment axes, global top-k (the
    coordinator), optionally reranked on original vectors.

The multi-pod dry-run lowers exactly these two programs on the production
mesh (configs/flash_ann.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import core
from repro.graph import backends as bk
from repro.kernels import ops
from repro.graph.beam import INF, beam_search
from repro.graph.hnsw import (
    HNSWIndex,
    HNSWParams,
    SearchResult,
    build_hnsw_jit,
    search_hnsw,
)
from repro.graph.index import AnnIndex
from repro.graph.rerank import (
    ExactReranker,
    RawVectors,
    SearchSpec,
    merge_rerank_topk,
    rerank_mode,
)


class SegmentedIndexes(NamedTuple):
    """Stacked per-segment indexes (leading axis = segment)."""

    index: HNSWIndex  # every leaf has a leading (S,) axis


def fit_shared_coder(
    key, sample: jax.Array, *, d_f: int, m_f: int, l_f: int = 4, h: int = 8,
    kmeans_iters: int = 25,
) -> core.FlashCoder:
    """Offline: fit one Flash coder for all segments (host-side eigh + jax
    k-means)."""
    return core.fit_flash(
        key, sample, d_f=d_f, m_f=m_f, l_f=l_f, h=h, kmeans_iters=kmeans_iters
    )


def build_segment(
    data_seg: jax.Array,
    coder: core.FlashCoder,
    levels: jax.Array,
    entries: jax.Array,
    *,
    params: HNSWParams,
) -> HNSWIndex:
    """Pure-jax single-segment build (traceable under shard_map/vmap).

    Each segment runs the same engine-driven program (graph/engine.py);
    ``params.width`` therefore widens every segment's CA stage at once.
    """
    codes = core.encode(coder, data_seg)
    backend = bk.FlashBackend(coder, codes)
    index, _ = build_hnsw_jit(data_seg, backend, levels, entries, params=params)
    return index


def build_segments_vmapped(
    data_segs: jax.Array,
    coder: core.FlashCoder,
    levels: jax.Array,
    entries: jax.Array,
    *,
    params: HNSWParams,
) -> SegmentedIndexes:
    """Reference/local form: the shard_map deployment's per-device program
    run on one device for each segment in turn, stacked on the segment axis.

    One segment per call, as on each device of the mesh: a vmap over all S
    segments at once batches the float matmuls differently, which can move
    a quantized table entry across a level boundary and change the graph.
    Used by tests and by single-host benchmarks.
    """
    f = jax.jit(jax.vmap(
        functools.partial(build_segment, params=params), in_axes=(0, None, 0, 0)
    ))
    parts = [
        f(data_segs[s : s + 1], coder, levels[s : s + 1], entries[s : s + 1])
        for s in range(data_segs.shape[0])
    ]
    index = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
    return SegmentedIndexes(index=index)


def make_segmented_build_fn(mesh, *, params: HNSWParams, seg_axes=("pod", "data")):
    """shard_map program: one segment per device group along ``seg_axes``.

    data_segs: (S, n_s, D) sharded so each device owns one (1, n_s, D) slice;
    the coder is replicated. Returns the stacked indexes with the same
    segment sharding.
    """
    axes = tuple(a for a in seg_axes if a in mesh.axis_names)
    spec_seg = P(axes)

    def per_device(data_seg, coder, levels, entries):
        # leading axis is the local segment count (1 per device group)
        f = functools.partial(build_segment, params=params)
        return jax.vmap(f, in_axes=(0, None, 0, 0))(data_seg, coder, levels, entries)

    def build(data_segs, coder, levels, entries):
        return jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(spec_seg, P(), spec_seg, spec_seg),
            out_specs=spec_seg,
            check_vma=False,
        )(data_segs, coder, levels, entries)

    return build


# ---------------------------------------------------------------------------
# Search with top-k merge (the inter-shard coordinator)
# ---------------------------------------------------------------------------


def search_segment(
    index: HNSWIndex,
    queries: jax.Array,
    *,
    k: int,
    ef_search: int,
    id_offset: jax.Array,
    max_layers: int | None = None,
    rerank_vectors: jax.Array | None = None,
):
    """Local search; returns globally-offset ids + distances.

    With ``rerank_vectors`` (the segment's original vectors) the returned
    distances are exact squared L2 — required for a correct cross-segment
    merge, since quantized ADC sums are only comparison-valid *within* a
    coder, not fine-grained enough to rank near-ties across segments.
    """
    res = search_hnsw(
        index, queries, k=k, ef_search=ef_search, max_layers=max_layers,
        rerank_vectors=rerank_vectors,
    )
    gids = jnp.where(res.ids >= 0, res.ids + id_offset, -1)
    return gids, res.dists


def make_segmented_search_fn(
    mesh, *, k: int, ef_search: int, max_layers: int | None = None,
    seg_axes=("pod", "data"),
):
    """shard_map program: fan-out search + two-stage top-k merge.

    queries are replicated to every segment; each device returns its local
    top-k; an ``all_gather`` along the segment axes collects (S·k) candidates
    per query and a global top-k picks the answer — the coordinator step.
    """
    axes = tuple(a for a in seg_axes if a in mesh.axis_names)
    spec_seg = P(axes)

    def per_device(index, queries, id_offset, seg_vectors):
        idx1 = jax.tree_util.tree_map(lambda x: x[0], index)  # local segment
        gids, d = search_segment(
            idx1, queries, k=k, ef_search=ef_search, max_layers=max_layers,
            id_offset=id_offset[0], rerank_vectors=seg_vectors[0],
        )
        # gather candidates from all segments: (S*k) per query
        all_ids = gids
        all_d = d
        for ax in axes:
            all_ids = jax.lax.all_gather(all_ids, ax, axis=1, tiled=True)
            all_d = jax.lax.all_gather(all_d, ax, axis=1, tiled=True)
        neg, pos = jax.lax.top_k(-all_d, k)
        out_ids = jnp.take_along_axis(all_ids, pos, axis=1)
        return out_ids, -neg

    def search(index_stack, queries, id_offsets, seg_vectors):
        return jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(spec_seg, P(), spec_seg, spec_seg),
            out_specs=(P(), P()),
            check_vma=False,
        )(index_stack, queries, id_offsets, seg_vectors)

    return search


# ---------------------------------------------------------------------------
# Per-segment facade with cross-segment maintenance (DESIGN.md §8)
# ---------------------------------------------------------------------------


class SegmentedAnnIndex:
    """S independent :class:`repro.index.AnnIndex` facades + a coordinator.

    The dynamic-maintenance face of the distributed layer: each segment is a
    full facade (so it can grow and tombstone in place), and this class owns
    the cross-segment concerns — global id assignment (stable insertion
    order across the whole collection), fan-out search with top-k merge,
    and **add routing**: new vectors go to the segment whose build-time
    centroid is nearest, i.e. growth preserves the locality the sharding
    started with. Centroids are frozen at build (like the shared coder);
    drift is absorbed by each segment's own maintenance.

    The mesh deployment above (``make_segmented_build_fn``) keeps the
    stacked/shard_map form for static fleets; this facade is the host-side
    serving form where segments evolve independently.
    """

    def __init__(self, segments, centroids, global_of, locate):
        self.segments = segments          # list[AnnIndex | None] (None = lost)
        self._centroids = centroids       # (S, D) routing table (frozen)
        self._global_of = global_of       # list[np int64]: local -> global
        self._locate = locate             # np (N, 2): global -> (seg, local)
        self._raw_cache = None            # (N, D) rerank corpus, built lazily
        #: segment indices whose payload failed verification at restore —
        #: quarantined: their vectors are unreachable, everything else serves
        self._quarantined = frozenset(
            s for s, seg in enumerate(segments) if seg is None
        )

    @classmethod
    def build(
        cls,
        data_segs,
        *,
        algo: str = "hnsw",
        backend: str = "flash",
        params: HNSWParams | None = None,
        seed: int = 0,
        backend_kwargs: dict | None = None,
        strategy: str = "bulk",
        **algo_kwargs,
    ) -> "SegmentedAnnIndex":
        """data_segs: (S, n_s, D) array or list of per-segment (n_s, D)
        arrays. Each segment fits its own coder (offline shared-coder
        deployments should build per-segment ``AnnIndex`` objects themselves
        and pass prebuilt backends). ``strategy`` is forwarded to every
        per-segment :meth:`AnnIndex.build` — segments are the natural unit
        for the bulk fast path (DESIGN.md §12): each one is a from-scratch
        build over its own shard.

        Segments are materialized ONE AT A TIME: ``data_segs`` may be a
        generator of per-segment arrays, and the loop converts, builds,
        and releases each slice before touching the next — peak memory is
        the largest single segment plus the coordinator's O(S·D) centroid
        table, never a second copy of the whole dataset (the old path
        converted every slice up front and ``jnp.stack``-ed centroids over
        the retained list; tests/test_sharded.py asserts the streaming
        bound). For chunked sources that do not arrive pre-sliced, use
        :meth:`build_streaming`."""
        segments, global_of, means = [], [], []
        next_gid = 0
        for s, seg_data in enumerate(data_segs):
            seg = jnp.asarray(seg_data, jnp.float32)
            del seg_data  # drop the source slice before building
            segments.append(AnnIndex.build(
                seg, algo=algo, backend=backend, params=params,
                seed=seed + s, backend_kwargs=backend_kwargs,
                strategy=strategy, **algo_kwargs,
            ))
            means.append(np.asarray(seg.mean(axis=0), np.float32))
            n_s = int(seg.shape[0])
            global_of.append(np.arange(next_gid, next_gid + n_s, dtype=np.int64))
            next_gid += n_s
        return cls.from_parts(segments, np.stack(means), global_of)

    @classmethod
    def from_parts(cls, segments, centroids, global_of) -> "SegmentedAnnIndex":
        """Assemble a collection from already-built segments.

        The adoption constructor behind every parallel producer: the
        sharded builder (graph/sharded.py) hands in pool-built or
        shard_map-built :class:`AnnIndex` objects plus its routing state,
        and this derives the global→(segment, local) locator from the
        per-segment id maps. ``global_of`` entries may be any permutation
        partition of [0, N) — ids keep stream order, not segment order."""
        global_of = [np.asarray(g, np.int64) for g in global_of]
        n = sum(int(g.shape[0]) for g in global_of)
        locate = np.empty((n, 2), np.int64)
        for s, gids in enumerate(global_of):
            locate[gids, 0] = s
            locate[gids, 1] = np.arange(gids.shape[0])
        return cls(
            segments, jnp.asarray(centroids, jnp.float32), global_of, locate
        )

    @classmethod
    def build_streaming(
        cls,
        source,
        *,
        n_segments: int,
        chunk_size: int = 65536,
        workers: int | None = None,
        mesh=None,
        workdir: str | None = None,
        snapshot_path: str | None = None,
        algo: str = "hnsw",
        backend: str = "flash_blocked",
        params: HNSWParams | None = None,
        seed: int = 0,
        backend_kwargs: dict | None = None,
        strategy: str = "bulk",
        **algo_kwargs,
    ) -> "SegmentedAnnIndex":
        """Build from a chunked stream via the sharded pipeline
        (DESIGN.md §16): nearest-centroid streaming assignment, then
        parallel per-segment builds — across ``mesh`` devices, a
        ``workers``-wide process pool, or inline (single-device fallback).
        ``source`` is an (n, D) array or a zero-arg callable returning a
        chunk iterator; the full dataset is never resident in this
        process. See :class:`repro.graph.sharded.ShardedBuilder` for the
        full control surface (plans, manifests, metrics)."""
        from repro.graph.sharded import ShardConfig, ShardedBuilder

        builder = ShardedBuilder(
            ShardConfig(
                n_segments=n_segments, chunk_size=chunk_size, algo=algo,
                backend=backend, params=params, strategy=strategy,
                backend_kwargs=backend_kwargs, algo_kwargs=algo_kwargs,
                seed=seed,
            ),
            workers=workers, mesh=mesh, workdir=workdir,
        )
        return builder.build(source, snapshot_path=snapshot_path).index

    @property
    def n(self) -> int:
        return int(self._locate.shape[0])

    @property
    def n_active(self) -> int:
        return sum(s.n_active for s in self.segments if s is not None)

    @property
    def quarantined(self) -> frozenset:
        """Indices of segments lost to corruption at restore (empty when
        healthy). Their ids stay allocated (global numbering is stable) but
        cannot be returned by search until a good snapshot is restored."""
        return self._quarantined

    def health(self) -> dict:
        """Degraded-serving surface (DESIGN.md §15): which segments are
        quarantined and how many ids that strands. Mirrors
        :meth:`repro.graph.index.AnnIndex.health` so ``Runtime.health``
        treats both uniformly."""
        lost = sum(len(self._global_of[s]) for s in self._quarantined)
        return {
            "healthy": not self._quarantined,
            "degraded": bool(self._quarantined),
            "n": self.n,
            "n_active": self.n_active,
            "n_segments": len(self.segments),
            "quarantined": sorted(self._quarantined),
            "lost_ids": int(lost),
            "lost_fraction": float(lost) / self.n if self.n else 0.0,
        }

    @property
    def centroids(self) -> jax.Array:
        """(S, D) frozen routing table (build-time segment means)."""
        return self._centroids

    def global_ids(self, s: int) -> np.ndarray:
        """Copy of segment ``s``'s local→global id map (``repro.serve``'s
        router maps per-segment results back to collection ids with this)."""
        return np.asarray(self._global_of[s], np.int64).copy()

    @property
    def raw_vectors(self) -> jax.Array:
        """(N, D) raw vectors in *global* id order — the collection-level
        rerank corpus (assembled lazily from the segments' tables,
        invalidated by ``add``). A global id that was routed to more than
        one segment (replicated deployments) resolves to its ``_locate``
        entry — one vector per id, like every other consumer."""
        if self._raw_cache is None or int(self._raw_cache.shape[0]) != self.n:
            d = int(self._centroids.shape[1])
            # zeros for quarantined segments' rows: their vectors are lost,
            # but search never surfaces their ids, so the placeholder rows
            # are only ever touched by shape-dependent code
            out = np.zeros((self.n, d), np.float32)
            for s, seg in enumerate(self.segments):
                if seg is not None:
                    out[self._global_of[s]] = np.asarray(seg.data)
            self._raw_cache = jnp.asarray(out)
        return self._raw_cache

    def reranker(self, mode: str = "exact"):
        """The collection-level second stage (None for ``"none"``): exact
        squared L2 over :attr:`raw_vectors`. Cross-segment merges *must*
        re-score — quantized sums are coder-local (DESIGN.md §5) — so the
        approximate ``"reconstruct"`` mode (whose decode is per-segment) is
        rejected here."""
        mode = rerank_mode(mode)
        if mode == "none":
            return None
        if mode == "reconstruct":
            raise ValueError(
                "reconstruct rerank is per-coder; a cross-segment merge "
                "needs rerank='exact' (or 'none' for single-coder fleets)"
            )
        return ExactReranker(RawVectors(self.raw_vectors))

    # ---- snapshot hooks (repro.serve, DESIGN.md §9) ---------------------

    def export_state(self) -> tuple[dict, dict, list]:
        """(meta, coordinator arrays, per-segment ``AnnIndex.export_state``
        tuples) — the cross-segment state is just the routing table and the
        global↔local id maps; each segment snapshots itself."""
        if self._quarantined:
            raise RuntimeError(
                f"cannot export a degraded collection: segments "
                f"{sorted(self._quarantined)} are quarantined (their data "
                "was lost to corruption) — snapshotting now would make the "
                "loss permanent"
            )
        meta = {"n_segments": len(self.segments)}
        arrays = {
            "centroids": np.asarray(self._centroids),
            "locate": self._locate.copy(),
        }
        for s, gids in enumerate(self._global_of):
            arrays[f"global_of.{s}"] = np.asarray(gids, np.int64)
        return meta, arrays, [seg.export_state() for seg in self.segments]

    @classmethod
    def restore(cls, meta: dict, arrays: dict, segments: list) -> "SegmentedAnnIndex":
        """Inverse of :meth:`export_state`. A ``None`` entry in ``segments``
        (how ``serve.load_index(..., quarantine=True)`` reports a
        CRC-failing segment) restores as quarantined: the collection serves
        the healthy remainder and :meth:`health` flags the damage."""
        segs = [
            None if st is None else AnnIndex.restore(st[0], st[1])
            for st in segments
        ]
        global_of = [
            np.asarray(arrays[f"global_of.{s}"], np.int64)
            for s in range(int(meta["n_segments"]))
        ]
        return cls(
            segs, jnp.asarray(arrays["centroids"]), global_of,
            np.asarray(arrays["locate"], np.int64),
        )

    def __len__(self) -> int:
        return self.n

    def search(
        self, queries, k: int = 10, *, ef: int = 64, width: int = 1,
        rerank: bool | str = True, rerank_mult: int | None = None,
        spec: SearchSpec | None = None, fanout: bool = True,
    ) -> SearchResult:
        """Fan out to every segment, merge global top-k (the coordinator) —
        the distributed face of the two-stage pipeline (DESIGN.md §11).

        Each segment runs the *scan* half only (``spec.scan_spec()``: its
        quantized candidate superset, no local rerank); the coordinator
        merges the union through the one shared second stage
        (``rerank.merge_rerank_topk``): dedup by global id, one exact
        re-score, global top-k. rerank=True is the meaningful default here:
        quantized sums are only comparison-valid within one coder, so a
        cross-segment merge needs exact distances (DESIGN.md §5);
        ``rerank=False`` keeps the legacy single-coder quantized merge.

        ``fanout`` (default) dispatches the per-segment scans on the shared
        fan-out thread pool instead of a sequential Python loop — compiled
        executables release the GIL, so S scans overlap; results are merged
        positionally and are identical either way (tests/test_sharded.py).
        """
        from repro.graph.sharded import fanout_map

        queries = jnp.asarray(queries, jnp.float32)
        if spec is None:
            spec = SearchSpec(
                k=k, ef=ef, width=width, rerank=rerank_mode(rerank),
                rerank_mult=rerank_mult,
            )
        reranker = self.reranker(spec.rerank)  # fail fast on bad modes
        scan = spec.scan_spec()
        live = [
            (s, seg) for s, seg in enumerate(self.segments) if seg is not None
        ]  # quarantined segments serve nothing; the remainder fans out

        def scan_one(item):
            s, seg = item
            res = seg.search(queries, spec=scan)
            gids = jnp.asarray(self._global_of[s], jnp.int32)
            ids = jnp.where(res.ids >= 0, gids[jnp.maximum(res.ids, 0)], -1)
            return ids, jnp.where(res.ids >= 0, res.dists, INF), res.n_scan

        results = fanout_map(scan_one, live, parallel=fanout)
        all_ids = [r[0] for r in results]
        all_d = [r[1] for r in results]
        n_scan = sum(
            (jnp.asarray(r[2], jnp.int32) for r in results), jnp.int32(0)
        )
        cat_ids = jnp.concatenate(all_ids, axis=1)  # (Q, S·n_keep)
        cat_d = jnp.concatenate(all_d, axis=1)
        ids, dists, n_rerank = merge_rerank_topk(
            reranker, queries, cat_ids, cat_d, spec.k
        )
        return SearchResult(
            ids=ids.astype(jnp.int32), dists=dists,
            n_dists=n_scan + n_rerank, n_scan=n_scan, n_rerank=n_rerank,
        )

    def add(self, new_vectors) -> np.ndarray:
        """Route each new vector to the nearest-centroid segment and grow
        that segment in place. Returns the global ids assigned (input
        order)."""
        new = jnp.asarray(new_vectors, jnp.float32)
        if new.ndim == 1:
            new = new[None]
        banned = None
        if self._quarantined:
            # degraded routing: never grow a lost segment — the nearest
            # *healthy* centroid takes the vector instead
            mask = np.zeros(len(self.segments), bool)
            mask[sorted(self._quarantined)] = True
            banned = jnp.asarray(mask)
        # the shared routing primitive: same kernel dispatch the streaming
        # sharded assignment and the serving router go through
        route, _ = ops.nearest_centroid(new, self._centroids, banned=banned)
        route = np.asarray(route)
        m = int(new.shape[0])
        gids = self.n + np.arange(m, dtype=np.int64)
        new_locate = np.empty((m, 2), np.int64)
        self._raw_cache = None  # collection rerank corpus grows
        for s, seg in enumerate(self.segments):
            rows = np.nonzero(route == s)[0]
            if rows.size == 0:
                continue
            local0 = seg.n
            seg.add(new[jnp.asarray(rows)])
            self._global_of[s] = np.concatenate(
                [self._global_of[s], gids[rows]]
            )
            new_locate[rows, 0] = s
            new_locate[rows, 1] = local0 + np.arange(rows.size)
        self._locate = np.concatenate([self._locate, new_locate])
        return gids

    def delete(self, global_ids) -> int:
        """Tombstone by global id; returns the number newly tombstoned."""
        gids = np.atleast_1d(np.asarray(global_ids, np.int64))
        if gids.size == 0:
            return 0
        if gids.min() < 0 or gids.max() >= self.n:
            raise IndexError(
                f"global ids must be in [0, {self.n}); got "
                f"[{gids.min()}, {gids.max()}]"
            )
        n_new = 0
        loc = self._locate[gids]
        for s, seg in enumerate(self.segments):
            if seg is None:
                continue  # id already unreachable; nothing to tombstone
            local = loc[loc[:, 0] == s, 1]
            if local.size:
                n_new += seg.delete(local)
        return n_new

    def compact(self) -> None:
        """Compact every segment (purge + rewire, see AnnIndex.compact)."""
        for seg in self.segments:
            if seg is not None:
                seg.compact()


def search_segments_local(
    seg: SegmentedIndexes,
    queries: jax.Array,
    seg_sizes: np.ndarray,
    *,
    k: int,
    ef_search: int,
    max_layers: int | None = None,
    seg_vectors: jax.Array | None = None,
):
    """Reference/local merge (vmap over segments + host top-k)."""
    s = jax.tree_util.tree_leaves(seg.index)[0].shape[0]
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(seg_sizes)[:-1]]), jnp.int32)

    def one_seg(index, off, vecs):
        return search_segment(
            index, queries, k=k, ef_search=ef_search, max_layers=max_layers,
            id_offset=off, rerank_vectors=vecs,
        )

    if seg_vectors is None:
        gids, dists = jax.vmap(
            lambda index, off: search_segment(
                index, queries, k=k, ef_search=ef_search,
                max_layers=max_layers, id_offset=off,
            )
        )(seg.index, offsets)
    else:
        gids, dists = jax.vmap(one_seg)(seg.index, offsets, seg_vectors)  # (S, Q, k)
    all_ids = jnp.transpose(gids, (1, 0, 2)).reshape(queries.shape[0], s * k)
    all_d = jnp.transpose(dists, (1, 0, 2)).reshape(queries.shape[0], s * k)
    neg, pos = jax.lax.top_k(-all_d, k)
    return jnp.take_along_axis(all_ids, pos, axis=1), -neg
