"""The plain reference: exact top-k and distances, in squared L2 or by
inner product.

A configuration's ``distance`` names its ranking: ``"l2"`` (the default)
is the squared L2 distance, ``"ip"`` the distance -<q, x>, hnswlib's
inner-product space without its constant 1, so that the top-k are the k
smallest distances in ascending order under either.

Straight ``jax.numpy`` over fixed blocks of the collection, the products at
``HIGHEST`` precision; nothing here imports the program under test. The
control of a cell is the same reference computed in bfloat16, put in the
program's place (``precision="bfloat16"``).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16")
DISTANCES = ("l2", "ip")
BLOCK = 1 << 16
#: words by which a configuration's prose ``metric`` names an inner product
IP_WORDS = re.compile(r"\b(ip|mips|inner[ _-]?product|dot[ _-]?product)\b", re.I)


def _validate(distance: str, precision: str = "float32") -> None:
    if distance not in DISTANCES:
        raise ValueError(f"unknown distance {distance!r}; known: {DISTANCES}")
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")


def distance_of(cfg: dict) -> str:
    """A configuration's ``distance``, ``"l2"`` where it states none.

    ``distance`` is what the harness ranks by; the prose ``metric``, where a
    configuration has one, has to agree with it: a ``metric`` that names an
    inner product without ``"distance": "ip"``, or the reverse, is refused
    rather than ranked by the default."""
    distance = cfg.get("distance", "l2")
    _validate(distance)
    metric = cfg.get("metric")
    if metric is not None and bool(IP_WORDS.search(str(metric))) != (distance == "ip"):
        raise ValueError(
            f"metric {metric!r} and distance {distance!r} disagree: a metric that "
            f"names an inner product needs \"distance\": \"ip\", and only it")
    return distance


def _cast(x, precision: str):
    return x.astype(jnp.bfloat16) if precision == "bfloat16" else x


@functools.partial(jax.jit, static_argnames=("precision", "distance"))
def _block_topk(x, q, best_d, best_i, offset, valid, *, precision, distance):
    xc, qc = _cast(x, precision), _cast(q, precision)
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
    if distance == "ip":
        d = -jnp.matmul(qc, xc.T, precision=prec).astype(jnp.float32)
    else:
        d = (
            jnp.sum(qc * qc, axis=1, keepdims=True).astype(jnp.float32)
            + jnp.sum(xc * xc, axis=1).astype(jnp.float32)[None, :]
            - 2.0 * jnp.matmul(qc, xc.T, precision=prec).astype(jnp.float32)
        )
    ids = offset + jnp.arange(x.shape[0], dtype=jnp.int32)
    d = jnp.where(ids[None, :] < valid, d, jnp.inf)
    all_d = jnp.concatenate([best_d, d], axis=1)
    all_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-all_d, best_d.shape[1])
    return -neg, jnp.take_along_axis(all_i, pos, axis=1)


def topk(data, queries, k: int, *, distance: str = "l2",
         precision: str = "float32"):
    """Exact top-k ids and distances, (Q, k) each, by blocks of rows."""
    _validate(distance, precision)
    data = jnp.asarray(data, jnp.float32)
    q = jnp.asarray(queries, jnp.float32)
    n = data.shape[0]
    block = min(n, BLOCK)
    pad = -n % block
    if pad:
        data = jnp.concatenate([data, jnp.zeros((pad, data.shape[1]), jnp.float32)])
    best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    for off in range(0, n + pad, block):
        best_d, best_i = _block_topk(
            data[off:off + block], q, best_d, best_i, jnp.int32(off),
            jnp.int32(n), precision=precision, distance=distance,
        )
    return np.asarray(best_i), np.asarray(best_d)


@functools.partial(jax.jit, static_argnames=("precision", "distance"))
def _dists(data, q, ids, *, precision, distance):
    rows = _cast(data[jnp.maximum(ids, 0)], precision)
    qc = _cast(q, precision)[:, None, :]
    if distance == "ip":
        return -jnp.sum(rows * qc, axis=-1).astype(jnp.float32)
    diff = rows - qc
    return jnp.sum(diff * diff, axis=-1).astype(jnp.float32)


def distances(data, queries, ids, *, distance: str = "l2",
              precision: str = "float32") -> np.ndarray:
    """The distance from each query to each of its ids, (Q, k); -1 ids
    read inf."""
    _validate(distance, precision)
    ids = np.asarray(ids, np.int32)
    d = np.asarray(_dists(
        jnp.asarray(data, jnp.float32), jnp.asarray(queries, jnp.float32),
        jnp.asarray(ids), precision=precision, distance=distance,
    ))
    return np.where(ids >= 0, d, np.inf)


def recall_at_k(got: np.ndarray, want: np.ndarray) -> float:
    """Share of the true top-k ids that ``got`` holds, over all queries."""
    hits = sum(len(set(g[g >= 0].tolist()) & set(w.tolist()))
               for g, w in zip(got, want))
    return hits / want.size
