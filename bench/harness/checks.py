"""The comparisons that decide ``correct``.

Each check is a number beside its limit. A build is judged by its graph
(ids in range, no self-edges, every vertex reachable from the entry on the
base layer) and by what a search of it returns; a served request by its
answer. Answers are compared with :mod:`reference`: the true top-k for
recall, and for each returned id its exact distance, which an exact rerank
has to reproduce.
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


def dist_gap(ids, dists, data, queries) -> float:
    """Widest gap between a returned distance and the exact one of the same
    id, as a share of the exact one (floored at a thousandth of the median
    exact distance)."""
    ref = reference.distances(data, queries, ids)
    ok = np.isfinite(ref) & (ids >= 0)
    if not ok.any():
        return float("inf")
    floor = 1e-3 * float(np.median(ref[ok]))
    got = np.asarray(dists, np.float64)
    gap = np.full(ref.shape, np.inf)
    gap[ok] = np.abs(got[ok] - ref[ok]) / np.maximum(ref[ok], floor)
    return float(gap.max())


def answer_checks(ids, dists, data, queries, truth, limits: dict) -> list[Check]:
    """Searched or served answers against the reference."""
    ids = np.asarray(ids)
    dists = np.asarray(dists)
    k = truth.shape[1]
    return [
        Check("malformed", float(malformed(ids[:, :k], dists[:, :k], data.shape[0])),
              0.0, "<="),
        Check("dist_gap", dist_gap(ids[:, :k], dists[:, :k], data, queries),
              float(limits["dist_gap"]), "<="),
        Check("recall_at_10", reference.recall_at_k(ids[:, :k], truth),
              float(limits["recall_at_10"]), ">="),
    ]
