"""`repro.index` — the unified ANN index facade (DESIGN.md §8).

The paper motivates Flash with indexing time becoming critical under
"dynamic index maintenance demand"; this module is the repo's answer to that
demand. One registry-backed type, :class:`AnnIndex`, fronts every graph
algorithm (HNSW / Vamana / NSG) over every distance backend
(``graph.backends.kinds()``), with one ``SearchResult`` shape for flat and
layered graphs — and, the new capability, **in-place maintenance**:

    index = AnnIndex.build(data, algo="hnsw", backend="flash_blocked")
    res   = index.search(queries, k=10, ef=96)        # one result shape
    index.add(new_vectors)      # grow the FROZEN graph: no coder refit,
                                # no rebuild — batch re-insertion through
                                # BuildEngine.insert_batch (A1's model)
    index.delete(ids)           # tombstone: traversable, never returned
    index.compact()             # purge tombstones + rewire around them

Why this shape (DESIGN.md §8):

  * ``add`` is exactly one more synchronous batch of the same build program
    the index was constructed with — the batch-synchronous insertion model
    (A1) makes incremental growth *free*: an add batch against the frozen
    current graph is indistinguishable from the next batch of the original
    build. The distance backend grows through ``backend.extend`` (codes for
    the new vectors under the frozen coder; for the Flash blocked layout
    also fresh mirror rows that fill in as edges commit).
  * ``delete`` tombstones: the mask is honored by ``beam_search`` at result
    extraction, so deleted vertices keep carrying traffic (removing them
    eagerly would disconnect the graph) but are never returned.
  * ``compact`` purges tombstones from every adjacency row and batch
    re-inserts the affected vertices — again the same engine program, made
    safe for re-insertion by the engine's self-exclusion and
    already-present reverse-edge guards.

New algorithms plug in by registering an :class:`AlgoSpec`; the facade never
reaches into algorithm internals (no underscore-private imports — lint-
enforced in tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph import backends as bk
from repro.graph.engine import (
    PHASE_NAMES,
    BuildEngine,
    BuildParams,
    BuildStats,
    batch_schedule,
    prefix_entries,
    run_insert_schedule,
    sample_levels,
)
from repro.graph.hnsw import HNSWIndex, SearchResult, build_hnsw, search_hnsw
from repro.graph.nsg import build_nsg
from repro.graph.rerank import SearchSpec, make_reranker, rerank_mode
from repro.graph.vamana import FlatIndex, build_vamana, search_flat_result

__all__ = [
    "AlgoSpec",
    "AnnIndex",
    "SearchResult",
    "SearchSpec",
    "algos",
    "grow_index",
    "register_algo",
]


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AlgoSpec:
    """One pluggable graph algorithm.

    builder(data, backend, params, seed, *, strategy, **algo_kwargs)
    -> (graph, stats) where ``graph`` is the algorithm's index pytree
    (HNSWIndex for layered, FlatIndex otherwise) and ``stats`` is anything
    with n_dists/n_hops (or None). ``strategy`` is the facade's
    construction mode (``"bulk"`` | ``"incremental"``, DESIGN.md §12) —
    every registered builder must accept it. ``layered`` selects the search
    routine and whether levels are sampled for added vectors.
    """

    name: str
    layered: bool
    default_params: BuildParams
    builder: Callable[..., tuple]


_REGISTRY: dict[str, AlgoSpec] = {}


def register_algo(spec: AlgoSpec) -> AlgoSpec:
    """Register (or replace) an algorithm; returns the spec for chaining."""
    _REGISTRY[spec.name] = spec
    return spec


def algos() -> tuple[str, ...]:
    """Registered algorithm names, registration order."""
    return tuple(_REGISTRY)


def _build_hnsw_adapter(
    data, backend, params, seed, *, strategy="incremental", levels=None
):
    return build_hnsw(
        data, backend, params=params, seed=seed, levels=levels,
        strategy=strategy,
    )


def _build_vamana_adapter(
    data, backend, params, seed, *, strategy="incremental", two_pass=True
):
    # seed only steers the bulk pools; the incremental schedule is
    # deterministic (medoid entry).
    return build_vamana(
        data, backend, params=params, two_pass=two_pass,
        strategy=strategy, seed=seed,
    )


def _build_nsg_adapter(
    data, backend, params, seed, *, strategy="incremental", knn_k=16
):
    index, _knn_adj = build_nsg(
        data, backend, params=params, knn_k=knn_k,
        strategy=strategy, seed=seed,
    )
    return index, None


register_algo(AlgoSpec(
    name="hnsw", layered=True,
    default_params=BuildParams(), builder=_build_hnsw_adapter,
))
register_algo(AlgoSpec(
    name="vamana", layered=False,
    default_params=BuildParams(alpha=1.2), builder=_build_vamana_adapter,
))
register_algo(AlgoSpec(
    name="nsg", layered=False,
    default_params=BuildParams(), builder=_build_nsg_adapter,
))

# Exact-type -> make_backend kind, for prebuilt backend instances (subclass
# lookup would misfile FlashBlockedBackend under "flash").
_KIND_OF_TYPE: dict[type, str] = {
    bk.FP32Backend: "fp32",
    bk.PCABackend: "pca",
    bk.SQBackend: "sq",
    bk.PQBackend: "pq",
    bk.FlashBackend: "flash",
    bk.FlashBlockedBackend: "flash_blocked",
}


# ---------------------------------------------------------------------------
# The device-side growth program (shared by add() and compact())
# ---------------------------------------------------------------------------


def grow_index(
    engine: BuildEngine, data, adj0, adj0_d, adj_up, adj_up_d, backend,
    levels, ids, entries, mask,
):
    """Run ``engine.insert_batch`` over a (nb, P) id schedule against an
    existing graph — the whole of dynamic maintenance, expressed as more
    batches of the original build program (DESIGN.md §8).

    A thin public alias for :func:`repro.graph.engine.run_insert_schedule`
    (one jitted program, also the bulk build's reachability-repair engine):
    ids/mask (nb, P): padded id batches; entries (nb,): per-batch entry
    point. Returns the updated graph arrays, backend, and a CostAccount of
    the growth's distance evaluations.
    """
    return run_insert_schedule(
        engine, data, adj0, adj0_d, adj_up, adj_up_d, backend,
        levels, ids, entries, mask,
    )


# Maintenance schedules share the engine's host-side batch padder.
_batch_schedule = batch_schedule


def _purge_rows(adj: np.ndarray, adj_d: np.ndarray, dead: np.ndarray):
    """Drop dead ids from every row (shift survivors left, order kept) and
    clear dead vertices' own rows. Returns (adj', adj_d', affected) where
    affected marks live rows that lost at least one neighbor."""
    keep = (adj >= 0) & ~dead[np.maximum(adj, 0)]
    affected = ((adj >= 0) & ~keep).any(axis=1) & ~dead
    order = np.argsort(~keep, axis=1, kind="stable")  # kept slots first
    adj2 = np.take_along_axis(np.where(keep, adj, -1), order, axis=1)
    adj_d2 = np.take_along_axis(np.where(keep, adj_d, np.inf), order, axis=1)
    adj2[dead] = -1
    adj_d2[dead] = np.inf
    return adj2, adj_d2.astype(np.float32), affected


def _as_stats(raw) -> BuildStats | None:
    if raw is None:
        return None
    phases = getattr(raw, "phases", None)
    return BuildStats(
        n_dists=jnp.asarray(raw.n_dists, jnp.float32),
        n_hops=jnp.asarray(raw.n_hops, jnp.float32),
        phases=None if phases is None else jnp.asarray(phases, jnp.float32),
    )


def _record_build(sp, stats: BuildStats | None) -> None:
    """Fold a finished build's cost into its span and the per-phase
    registry counters (obs-enabled paths only; ``sp`` is the null span
    otherwise, and the counters are skipped)."""
    if stats is None or not obs.enabled():
        return
    sp.add_cost(stats.n_dists, stats.n_hops)
    phases = stats.phase_dict()
    if phases is not None:
        sp.set(phases=phases)
        for name, v in phases.items():
            if v:
                obs.tick("build_dists_total", n=float(v), phase=name)


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------


class AnnIndex:
    """One index API over every registered algorithm and backend.

    Construct through :meth:`build`; the instance owns the algorithm's graph
    pytree, the raw vectors (for exact rerank), and the tombstone mask. Ids
    are stable insertion-order positions: the i-th vector ever given to the
    index (build data first, then ``add`` batches in order) is id i, and
    deletions never renumber.
    """

    def __init__(self, *, spec, params, graph, data, backend_kind, seed,
                 stats=None, strategy="incremental"):
        self._spec = spec
        self.params = params
        self._graph = graph
        self._data = data
        self.backend_kind = backend_kind
        self.build_strategy = strategy
        self._seed = seed
        self._n_adds = 0
        self._tombs = np.zeros(int(data.shape[0]), bool)
        self._retired = np.zeros(int(data.shape[0]), bool)
        self._banned_dev = None  # device copy of _tombs, built lazily
        self.last_stats = stats

    # ---- construction ---------------------------------------------------

    @classmethod
    def build(
        cls,
        data,
        *,
        algo: str = "hnsw",
        backend: str | Any = "flash_blocked",
        params: BuildParams | None = None,
        seed: int = 0,
        backend_kwargs: dict | None = None,
        strategy: str = "bulk",
        **algo_kwargs,
    ) -> "AnnIndex":
        """Build an index over ``data``.

        algo      one of :func:`algos` (``hnsw`` | ``vamana`` | ``nsg``).
        backend   a ``graph.backends.kinds()`` name (the coder is fitted on
                  ``data`` with ``backend_kwargs``) or a prebuilt backend
                  instance (then ``backend_kwargs`` must be empty).
        params    BuildParams; defaults to the algorithm's registered set.
        strategy  from-scratch construction mode (DESIGN.md §12):
                  ``"bulk"`` (default) bootstraps the graph with batched
                  RNN-Descent refinement rounds — much higher build
                  throughput at matching recall; ``"incremental"`` is the
                  paper's batch-synchronous insertion loop. Either way,
                  :meth:`add` routes through ``BuildEngine.insert_batch``
                  (dynamic growth is always incremental).
        algo_kwargs  forwarded to the algorithm builder (e.g. ``knn_k`` for
                  nsg, ``two_pass`` for vamana, ``levels`` for hnsw).
        """
        spec = _REGISTRY.get(algo)
        if spec is None:
            raise ValueError(
                f"unknown algo {algo!r}; registered: {', '.join(algos())}"
            )
        if strategy not in ("bulk", "incremental"):
            raise ValueError(
                f"unknown build strategy {strategy!r}; "
                "valid: 'bulk', 'incremental'"
            )
        params = spec.default_params if params is None else params
        if isinstance(backend, str):
            if backend not in bk.kinds():
                raise ValueError(
                    f"unknown backend kind {backend!r}; valid kinds: "
                    f"{', '.join(bk.kinds())}"
                )
            kind = backend
        else:
            if backend_kwargs:
                raise ValueError(
                    "backend_kwargs only apply when backend is a kind "
                    "string; got a prebuilt backend instance"
                )
            be = backend
            kind = _KIND_OF_TYPE.get(type(backend), "custom")
        # the root span holds the coder's fit and encode too: its host work
        # is part of what a build costs
        with obs.span(
            "build", algo=algo, strategy=strategy, backend=kind, n=len(data),
        ) as sp:
            data = jnp.asarray(data, jnp.float32)
            if isinstance(backend, str):
                kw = dict(backend_kwargs or {})
                if backend == "flash_blocked":
                    kw.setdefault("r_for_blocked", params.r_base)
                with obs.span("build/coder", kind=kind):
                    be = bk.make_backend(
                        backend, data, jax.random.PRNGKey(seed), **kw
                    )
            graph, raw_stats = spec.builder(
                data, be, params, seed, strategy=strategy, **algo_kwargs
            )
            stats = _as_stats(raw_stats)
            _record_build(sp, stats)
        return cls(
            spec=spec, params=params, graph=graph, data=data,
            backend_kind=kind, seed=seed, stats=stats,
            strategy=strategy,
        )

    @classmethod
    def from_graph(
        cls,
        graph,
        data,
        *,
        algo: str = "hnsw",
        params: BuildParams | None = None,
        backend_kind: str = "flash",
        seed: int = 0,
        stats: BuildStats | None = None,
        strategy: str = "incremental",
    ) -> "AnnIndex":
        """Wrap an already-built algorithm pytree in the facade.

        The adoption path for graphs constructed outside :meth:`build` —
        e.g. one segment sliced out of a ``shard_map``/vmapped stacked
        build (graph/segmented.py): the mesh program emits raw
        ``HNSWIndex`` pytrees, and this turns each into a full facade
        (searchable, growable, snapshot-able) without re-fitting or
        re-building anything. ``data`` is the segment's raw vectors in
        local id order (the rerank corpus); the graph's backend comes
        with the pytree."""
        spec = _REGISTRY.get(algo)
        if spec is None:
            raise ValueError(
                f"unknown algo {algo!r}; registered: {', '.join(algos())}"
            )
        data = jnp.asarray(data, jnp.float32)
        return cls(
            spec=spec,
            params=spec.default_params if params is None else params,
            graph=graph, data=data, backend_kind=backend_kind, seed=seed,
            stats=stats, strategy=strategy,
        )

    # ---- introspection --------------------------------------------------

    @property
    def algo(self) -> str:
        return self._spec.name

    @property
    def layered(self) -> bool:
        """Whether the graph is layered (HNSW-style) or flat (Vamana/NSG)."""
        return self._spec.layered

    @property
    def tombstones(self) -> np.ndarray:
        """Copy of the (n,) tombstone mask (True = deleted, not compacted)."""
        return self._tombs.copy()

    @property
    def graph(self):
        """The underlying algorithm index pytree (HNSWIndex / FlatIndex)."""
        return self._graph

    @property
    def backend(self):
        return self._graph.backend

    @property
    def data(self) -> jax.Array:
        """Raw vectors in id order (the rerank corpus)."""
        return self._data

    @property
    def n(self) -> int:
        """Total id slots ever allocated (including tombstoned/retired)."""
        return int(self._data.shape[0])

    @property
    def n_active(self) -> int:
        return int(self.n - (self._tombs | self._retired).sum())

    @property
    def deleted_ids(self) -> np.ndarray:
        return np.nonzero(self._tombs)[0]

    def __len__(self) -> int:
        return self.n

    def health(self) -> dict:
        """Degradation surface shared with :class:`SegmentedAnnIndex` (the
        serving stack's ``Runtime.health`` consumes either): a single-facade
        index has no quarantine-able parts, so it is healthy whenever it is
        loaded at all."""
        return {
            "healthy": True,
            "degraded": False,
            "n": self.n,
            "n_active": self.n_active,
        }

    def __repr__(self) -> str:
        return (
            f"AnnIndex(algo={self.algo!r}, backend={self.backend_kind!r}, "
            f"n={self.n}, active={self.n_active})"
        )

    # ---- search (the two-stage pipeline, DESIGN.md §11) -----------------

    def reranker(self, mode: str = "exact"):
        """The second-stage :class:`~repro.graph.rerank.Reranker` this index
        serves ``mode`` with (None for ``"none"``): exact rerank prefers the
        backend's retained raw table (``keep_raw=True`` builds, fp32) and
        falls back to the facade's own vector copy; ``"reconstruct"``
        decodes through the backend's coder."""
        return make_reranker(mode, backend=self.backend, raw_vectors=self._data)

    def search(
        self,
        queries,
        k: int = 10,
        *,
        ef: int = 64,
        width: int = 1,
        rerank: bool | str = True,
        rerank_mult: int | None = None,
        spec: SearchSpec | None = None,
    ) -> SearchResult:
        """Batched top-k search; one result shape for every algorithm.

        Every call is the two-stage pipeline of DESIGN.md §11: a quantized
        candidate scan (beam of ``ef``, best ``min(ef, k·rerank_mult)``
        retained) composed with a shared second stage. ``rerank`` picks the
        second stage: True / ``"exact"`` re-scores on raw vectors (exact
        squared L2 — the right default for every compact-code backend),
        False / ``"none"`` passes scan distances through unchanged, and
        ``"reconstruct"`` re-scores on coder-decoded vectors (approximate,
        zero extra memory). ``rerank_mult=None`` reranks the whole beam.
        A full ``spec=``:class:`SearchSpec` overrides the keyword knobs.
        """
        queries = jnp.asarray(queries, jnp.float32)
        single = queries.ndim == 1
        if single:
            queries = queries[None]
        if spec is None:
            spec = SearchSpec(
                k=k, ef=ef, width=width, rerank=rerank_mode(rerank),
                rerank_mult=rerank_mult,
            )
        reranker = self.reranker(spec.rerank)
        if self._banned_dev is None and self._tombs.any():
            self._banned_dev = jnp.asarray(self._tombs)
        banned = self._banned_dev
        search = search_hnsw if self._spec.layered else search_flat_result
        res = search(
            self._graph, queries, spec=spec, reranker=reranker, banned=banned
        )
        if single:
            res = res._replace(ids=res.ids[0], dists=res.dists[0])
        return res

    # ---- snapshot hooks (repro.serve, DESIGN.md §9) ---------------------

    def export_state(self) -> tuple[dict, dict]:
        """Everything needed to rebuild this index bit-exactly.

        Returns ``(meta, arrays)``: ``meta`` is JSON-serializable (algo,
        backend identity, build params, maintenance counters); ``arrays`` is
        a flat name → ``np.ndarray`` dict covering the graph arrays, raw
        vectors, tombstone/retired masks, and the full backend state
        (``backend.*``-prefixed, via ``backend.state_dict``). The file
        format around this lives in :mod:`repro.serve.snapshot`."""
        meta = {
            "algo": self.algo,
            "layered": self._spec.layered,
            "backend_kind": self.backend_kind,
            "backend_class": type(self.backend).__name__,
            "params": dataclasses.asdict(self.params),
            "seed": int(self._seed),
            "n_adds": int(self._n_adds),
            "strategy": self.build_strategy,
        }
        g = self._graph
        arrays = {
            "data": np.asarray(self._data),
            "tombs": self._tombs.copy(),
            "retired": self._retired.copy(),
            "entry": np.asarray(g.entry),
        }
        if self._spec.layered:
            arrays.update(
                adj0=np.asarray(g.adj0), adj0_d=np.asarray(g.adj0_d),
                adj_up=np.asarray(g.adj_up), adj_up_d=np.asarray(g.adj_up_d),
                levels=np.asarray(g.levels),
            )
        else:
            arrays.update(adj=np.asarray(g.adj), adj_d=np.asarray(g.adj_d))
        for name, arr in self.backend.state_dict().items():
            arrays[f"backend.{name}"] = arr
        return meta, arrays

    @classmethod
    def restore(cls, meta: dict, arrays: dict) -> "AnnIndex":
        """Inverse of :meth:`export_state` — rebuilds a live index whose
        ``search`` results are identical to the exported instance's."""
        spec = _REGISTRY.get(meta["algo"])
        if spec is None:
            raise ValueError(
                f"snapshot needs unregistered algo {meta['algo']!r}; "
                f"registered: {', '.join(algos())}"
            )
        if bool(meta["layered"]) != spec.layered:
            raise ValueError(
                f"algo {meta['algo']!r} is registered as "
                f"{'layered' if spec.layered else 'flat'} but the snapshot "
                f"was taken from a {'layered' if meta['layered'] else 'flat'} "
                "index"
            )
        be_cls = bk.CLASSES.get(meta["backend_class"])
        if be_cls is None:
            raise ValueError(
                f"unknown backend class {meta['backend_class']!r}; custom "
                "backends must be registered in graph.backends.CLASSES to "
                "be restorable"
            )
        backend = be_cls.from_state({
            name[len("backend."):]: arr
            for name, arr in arrays.items() if name.startswith("backend.")
        })
        entry = jnp.asarray(arrays["entry"], jnp.int32)
        if spec.layered:
            graph = HNSWIndex(
                adj0=jnp.asarray(arrays["adj0"]),
                adj0_d=jnp.asarray(arrays["adj0_d"]),
                adj_up=jnp.asarray(arrays["adj_up"]),
                adj_up_d=jnp.asarray(arrays["adj_up_d"]),
                levels=jnp.asarray(arrays["levels"]),
                entry=entry, backend=backend,
            )
        else:
            graph = FlatIndex(
                adj=jnp.asarray(arrays["adj"]),
                adj_d=jnp.asarray(arrays["adj_d"]),
                entry=entry, backend=backend,
            )
        obj = cls(
            spec=spec, params=BuildParams(**meta["params"]), graph=graph,
            data=jnp.asarray(arrays["data"]),
            backend_kind=meta["backend_kind"], seed=int(meta["seed"]),
            # pre-§12 snapshots predate the strategy field (all incremental)
            strategy=meta.get("strategy", "incremental"),
        )
        obj._n_adds = int(meta["n_adds"])
        obj._tombs = np.asarray(arrays["tombs"], bool).copy()
        obj._retired = np.asarray(arrays["retired"], bool).copy()
        return obj

    def clone(self) -> "AnnIndex":
        """A fully independent copy of this index — the generation-safe
        state hand-off (DESIGN.md §13).

        Round-trips through :meth:`export_state`/:meth:`restore`, so the
        clone is exactly as decoupled as a snapshot load: its graph arrays,
        backend state, raw vectors, and tombstone/retired masks share no
        mutable state with the original, and maintenance applied to either
        side (``add``/``delete``/``compact``) is invisible to the other.
        ``serve.IndexHandle`` builds every copy-on-write generation through
        this hook; searches on the clone are bit-exact with the source at
        clone time (the snapshot contract, tests/test_serve.py).
        """
        return type(self).restore(*self.export_state())

    # ---- dynamic maintenance -------------------------------------------

    def _maint_params(self) -> BuildParams:
        """Engine params for maintenance: flat algorithms insert as a
        single-layer build regardless of the user's max_layers."""
        if self._spec.layered:
            return self.params
        return dataclasses.replace(self.params, max_layers=1)

    def _graph_arrays(self):
        """(adj0, adj0_d, adj_up, adj_up_d) in engine layout; flat graphs
        get a zero-length upper stack."""
        g = self._graph
        if self._spec.layered:
            return g.adj0, g.adj0_d, g.adj_up, g.adj_up_d
        params = self._maint_params()
        n = g.adj.shape[0]
        adj_up = jnp.zeros((0, n, params.r_upper), jnp.int32)
        adj_up_d = jnp.zeros((0, n, params.r_upper), jnp.float32)
        return g.adj, g.adj_d, adj_up, adj_up_d

    def add(self, new_vectors) -> BuildStats:
        """Insert a batch of vectors into the existing frozen graph.

        No rebuild, no coder refit: the backend grows via
        ``backend.extend`` (new codes under the frozen coder) and the new
        vertices run through ``BuildEngine.insert_batch`` exactly like the
        next batches of the original build (DESIGN.md §8). Returns the
        growth's build stats (distance evaluations, hops); new ids are
        ``range(old_n, old_n + m)`` in input order.

        Cost note: ``grow_index`` is shape-specialized, so an add with a new
        (n, m) pair pays one XLA trace+compile; steady-state pipelines
        should batch adds (or keep batch sizes uniform) to amortize it.
        """
        new = jnp.asarray(new_vectors, jnp.float32)
        if new.ndim == 1:
            new = new[None]
        if new.shape[-1] != self._data.shape[1]:
            raise ValueError(
                f"dim mismatch: index is d={self._data.shape[1]}, "
                f"got d={new.shape[-1]}"
            )
        m = int(new.shape[0])
        zero = BuildStats(n_dists=jnp.float32(0), n_hops=jnp.float32(0))
        if m == 0:
            return zero
        n_old = self.n
        params = self._maint_params()
        g = self._graph
        self._n_adds += 1

        # Levels + per-batch entry plan (prefix_entries continued from the
        # built prefix, seeded with the live graph's entry point).
        if self._spec.layered:
            lv_old = np.asarray(g.levels)
            lv_new = sample_levels(
                self._seed + 7919 * self._n_adds, m,
                r_upper=params.r_upper, max_layers=params.max_layers,
            )
            levels_all = np.concatenate([lv_old, lv_new]).astype(np.int32)
        else:
            levels_all = np.zeros(n_old + m, np.int32)
        cur = int(g.entry)
        ent = prefix_entries(
            levels_all, params.batch, start=n_old, entry0=cur
        )
        # Final entry: a new vertex displaces the current entry only if it
        # strictly out-levels it (ties keep the incumbent; retired vertices
        # have level 0 and can never win a strict comparison).
        cand = int(np.argmax(levels_all))
        best = cand if levels_all[cand] > levels_all[cur] else cur

        ids, mask = _batch_schedule(
            np.arange(n_old, n_old + m, dtype=np.int32), params.batch
        )

        # Grow the graph arrays and the backend, then run the insert loop.
        adj0, adj0_d, adj_up, adj_up_d = self._graph_arrays()
        r_base = adj0.shape[1]
        adj0 = jnp.concatenate([adj0, jnp.full((m, r_base), -1, jnp.int32)])
        adj0_d = jnp.concatenate(
            [adj0_d, jnp.full((m, r_base), jnp.inf, adj0_d.dtype)]
        )
        l_up, _, r_up = adj_up.shape
        adj_up = jnp.concatenate(
            [adj_up, jnp.full((l_up, m, r_up), -1, jnp.int32)], axis=1
        )
        adj_up_d = jnp.concatenate(
            [adj_up_d, jnp.full((l_up, m, r_up), jnp.inf, adj_up_d.dtype)],
            axis=1,
        )
        backend = g.backend.extend(new)
        data_all = jnp.concatenate([self._data, new])

        with obs.span("build/add", algo=self.algo, m=m) as sp:
            adj0, adj0_d, adj_up, adj_up_d, backend, acct = grow_index(
                BuildEngine(params), data_all, adj0, adj0_d, adj_up, adj_up_d,
                backend, jnp.asarray(levels_all), jnp.asarray(ids),
                jnp.asarray(ent), jnp.asarray(mask),
            )
            stats = BuildStats(
                n_dists=acct.n_dists.astype(jnp.float32), n_hops=acct.n_hops,
                phases=acct.phases,
            )
            _record_build(sp, stats)

        if self._spec.layered:
            self._graph = g._replace(
                adj0=adj0, adj0_d=adj0_d, adj_up=adj_up, adj_up_d=adj_up_d,
                levels=jnp.asarray(levels_all),
                entry=jnp.int32(best), backend=backend,
            )
        else:
            # Medoid drift from growth is accepted (recomputed on compact).
            self._graph = g._replace(adj=adj0, adj_d=adj0_d, backend=backend)
        self._data = data_all
        self._tombs = np.concatenate([self._tombs, np.zeros(m, bool)])
        self._retired = np.concatenate([self._retired, np.zeros(m, bool)])
        self._banned_dev = None  # mask length changed
        self.last_stats = stats
        return stats

    def delete(self, ids) -> int:
        """Tombstone vertices: still traversable (they keep carrying search
        traffic so the graph stays connected) but never returned by
        :meth:`search`. Returns the number newly tombstoned; idempotent."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if ids.size == 0:
            return 0
        if ids.min() < 0 or ids.max() >= self.n:
            raise IndexError(
                f"delete ids must be in [0, {self.n}); got "
                f"[{ids.min()}, {ids.max()}]"
            )
        newly = int((~(self._tombs | self._retired)[ids]).sum())
        self._tombs[ids] = True
        self._banned_dev = None
        return newly

    def compact(self) -> BuildStats:
        """Physically rewire around tombstones.

        Purges tombstoned ids from every adjacency row (and the Flash
        blocked mirror), clears their own rows, then batch re-inserts every
        vertex that lost a neighbor through the same engine program as
        :meth:`add` — tombstoned slots become permanently retired
        (disconnected; ids are never reused). Returns the rewiring's build
        stats."""
        zero = BuildStats(n_dists=jnp.float32(0), n_hops=jnp.float32(0))
        if not self._tombs.any():
            return zero
        g = self._graph
        params = self._maint_params()
        dead = self._tombs.copy()
        gone = dead | self._retired
        active = ~gone

        # Host-side purge of every layer's rows.
        adj0, adj0_d, aff0 = _purge_rows(
            np.asarray(g.adj0 if self._spec.layered else g.adj),
            np.asarray(g.adj0_d if self._spec.layered else g.adj_d),
            dead,
        )
        affected = aff0
        up_layers = []
        if self._spec.layered:
            for l in range(g.adj_up.shape[0]):
                a, d, aff = _purge_rows(
                    np.asarray(g.adj_up[l]), np.asarray(g.adj_up_d[l]), dead
                )
                up_layers.append((a, d))
                affected |= aff
        affected &= active

        # New entry point over the survivors.
        if self._spec.layered:
            levels = np.asarray(g.levels).copy()
            levels[gone] = 0
            entry = (
                int(np.argmax(np.where(active, levels, -1)))
                if active.any() else int(g.entry)
            )
        else:
            levels = np.zeros(self.n, np.int32)
            entry = int(g.entry)
            if gone[entry] and active.any():
                data_np = np.asarray(self._data)
                mean = data_np[active].mean(axis=0)
                d = ((data_np - mean) ** 2).sum(axis=1)
                d[gone] = np.inf
                entry = int(np.argmin(d))

        adj0_j = jnp.asarray(adj0)
        adj0_d_j = jnp.asarray(adj0_d)
        if self._spec.layered:
            adj_up_j = (
                jnp.stack([jnp.asarray(a) for a, _ in up_layers])
                if up_layers else g.adj_up[:0]
            )
            adj_up_d_j = (
                jnp.stack([jnp.asarray(d) for _, d in up_layers])
                if up_layers else g.adj_up_d[:0]
            )
        else:
            adj_up_j = jnp.zeros((0, self.n, params.r_upper), jnp.int32)
            adj_up_d_j = jnp.zeros((0, self.n, params.r_upper), jnp.float32)
        # Resync the blocked neighbor-code mirror with the purged base layer
        # (no-op hook for every other backend).
        backend = g.backend.with_updated_edges(
            jnp.arange(self.n, dtype=jnp.int32), adj0_j
        )

        acct_stats = zero
        aff_ids = np.nonzero(affected)[0].astype(np.int32)
        if aff_ids.size:
            ids, mask = _batch_schedule(aff_ids, params.batch)
            ent = np.full((ids.shape[0],), entry, np.int32)
            with obs.span(
                "build/compact", algo=self.algo, rewired=int(aff_ids.size)
            ) as sp:
                adj0_j, adj0_d_j, adj_up_j, adj_up_d_j, backend, acct = (
                    grow_index(
                        BuildEngine(params), self._data, adj0_j, adj0_d_j,
                        adj_up_j, adj_up_d_j, backend, jnp.asarray(levels),
                        jnp.asarray(ids), jnp.asarray(ent), jnp.asarray(mask),
                    )
                )
                acct_stats = BuildStats(
                    n_dists=acct.n_dists.astype(jnp.float32),
                    n_hops=acct.n_hops, phases=acct.phases,
                )
                _record_build(sp, acct_stats)

        if self._spec.layered:
            self._graph = g._replace(
                adj0=adj0_j, adj0_d=adj0_d_j, adj_up=adj_up_j,
                adj_up_d=adj_up_d_j, levels=jnp.asarray(levels),
                entry=jnp.int32(entry), backend=backend,
            )
        else:
            self._graph = g._replace(
                adj=adj0_j, adj_d=adj0_d_j, entry=jnp.int32(entry),
                backend=backend,
            )
        self._retired |= dead
        self._tombs = np.zeros(self.n, bool)
        self._banned_dev = None
        self.last_stats = acct_stats
        return acct_stats
