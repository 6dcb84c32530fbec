"""The plain reference: exact squared-L2 top-k and distances.

Straight ``jax.numpy`` over fixed blocks of the collection, the products at
``HIGHEST`` precision; nothing here imports the program under test. The
control of a cell is the same reference computed in bfloat16, put in the
program's place (``precision="bfloat16"``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16")
BLOCK = 1 << 16


def _cast(x, precision: str):
    return x.astype(jnp.bfloat16) if precision == "bfloat16" else x


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_topk(x, q, best_d, best_i, offset, valid, *, precision):
    xc, qc = _cast(x, precision), _cast(q, precision)
    prec = jax.lax.Precision.HIGHEST if precision == "float32" else None
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


def topk(data, queries, k: int, *, precision: str = "float32"):
    """Exact top-k ids and distances, (Q, k) each, by blocks of rows."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
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
            jnp.int32(n), precision=precision,
        )
    return np.asarray(best_i), np.asarray(best_d)


@functools.partial(jax.jit, static_argnames=("precision",))
def _dists(data, q, ids, *, precision):
    rows = _cast(data[jnp.maximum(ids, 0)], precision)
    diff = rows - _cast(q, precision)[:, None, :]
    return jnp.sum(diff * diff, axis=-1).astype(jnp.float32)


def distances(data, queries, ids, *, precision: str = "float32") -> np.ndarray:
    """Squared L2 from each query to each of its ids, (Q, k); -1 ids read inf."""
    ids = np.asarray(ids, np.int32)
    d = np.asarray(_dists(
        jnp.asarray(data, jnp.float32), jnp.asarray(queries, jnp.float32),
        jnp.asarray(ids), precision=precision,
    ))
    return np.where(ids >= 0, d, np.inf)


def recall_at_k(got: np.ndarray, want: np.ndarray) -> float:
    """Share of the true top-k ids that ``got`` holds, over all queries."""
    hits = sum(len(set(g[g >= 0].tolist()) & set(w.tolist()))
               for g, w in zip(got, want))
    return hits / want.size
