"""The comparisons that decide ``correct``.

Each check is a number beside its limit. A build is judged by its graph
(ids in range, no self-edges, every vertex reachable from the entry on the
base layer) and by what a search of it returns; a served request by its
answer. Answers are compared with :mod:`reference`, in the configuration's
distance: the true top-k for recall, and for each returned id its exact
distance, which an exact rerank has to reproduce.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from harness import reference


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float
    op: str  # "<=" | ">="

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value <= self.limit if self.op == "<=" else self.value >= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit, "op": self.op}

    def line(self) -> str:
        return f"check {self.name}={self.value!r} limit {self.op} {self.limit!r}"


def reachable(adj: np.ndarray, entry: int) -> np.ndarray:
    """Vertices reachable from ``entry`` over the rows of ``adj`` (-1 = none)."""
    n = adj.shape[0]
    seen = np.zeros(n, bool)
    seen[entry] = True
    frontier = np.asarray([entry])
    while frontier.size:
        nb = adj[frontier].ravel()
        nb = np.unique(nb[(nb >= 0) & (nb < n)])
        frontier = nb[~seen[nb]]
        seen[frontier] = True
    return seen


def bad_edges(adj: np.ndarray) -> int:
    """Edges that point out of range or back to their own vertex."""
    n = adj.shape[0]
    own = np.arange(n)[:, None]
    return int(np.sum(((adj < -1) | (adj >= n)) | (adj == own)))


def graph_checks(adj0: np.ndarray, adj_up: np.ndarray, entry: int) -> list[Check]:
    """The built graph's shape: edges valid, base layer reachable."""
    n = adj0.shape[0]
    bad = bad_edges(adj0) + sum(bad_edges(a) for a in adj_up)
    unreached = n - int(reachable(adj0, int(entry)).sum()) if 0 <= entry < n else n
    return [
        Check("graph_bad_edges", float(bad), 0.0, "<="),
        Check("unreachable", float(unreached), 0.0, "<="),
    ]


def malformed(ids: np.ndarray, dists: np.ndarray, n: int) -> int:
    """Answers with a missing, out-of-range or repeated id, or distances out
    of order."""
    bad = 0
    for row, d in zip(ids, dists):
        if (row < 0).any() or (row >= n).any() or len(set(row.tolist())) < row.size:
            bad += 1
        elif not np.all(np.diff(d) >= 0):
            bad += 1
    return bad


def dist_gap(ids, dists, data, queries, *, distance: str = "l2") -> float:
    """Widest gap between a returned distance and the exact one of the same
    id. Under ``"l2"`` as a share of the exact one (floored at a thousandth
    of the median exact distance); under ``"ip"`` as a share of
    ||q||·||x_id||, the largest value <q, x_id> can take, since the rounding
    of a float32 dot product scales with that and not with <q, x_id>, which
    can be negative or near 0."""
    ref = reference.distances(data, queries, ids, distance=distance)
    ok = np.isfinite(ref) & (ids >= 0)
    if not ok.any():
        return float("inf")
    got = np.asarray(dists, np.float64)
    if distance == "ip":
        rows = np.asarray(data)[np.maximum(ids, 0)].astype(np.float64)
        q = np.asarray(queries, np.float64)
        scale = np.linalg.norm(q, axis=1)[:, None] * np.linalg.norm(rows, axis=-1)
        # an id at the origin: its exact distance is 0, and only 0 is no gap
        scale = np.maximum(scale, np.finfo(np.float64).tiny)
    else:
        scale = np.maximum(ref, 1e-3 * float(np.median(ref[ok])))
    gap = np.full(ref.shape, np.inf)
    gap[ok] = np.abs(got[ok] - ref[ok]) / scale[ok]
    return float(gap.max())


def answer_checks(ids, dists, data, queries, truth, limits: dict, *,
                  distance: str = "l2") -> list[Check]:
    """Searched or served answers against the reference, in the
    configuration's ``distance``."""
    ids = np.asarray(ids)
    dists = np.asarray(dists)
    k = truth.shape[1]
    return [
        Check("malformed", float(malformed(ids[:, :k], dists[:, :k], data.shape[0])),
              0.0, "<="),
        Check("dist_gap", dist_gap(ids[:, :k], dists[:, :k], data, queries,
                                   distance=distance),
              float(limits["dist_gap"]), "<="),
        Check("recall_at_10", reference.recall_at_k(ids[:, :k], truth),
              float(limits["recall_at_10"]), ">="),
    ]
