"""Batched k-means for subspace codebooks (paper §3.3.3, Eq. 8).

Flash (and PQ) need one codebook per subspace. Rather than looping Python-side
over the ``M_F`` subspaces we fit them *batched*: a single jitted program runs
k-means++ seeding plus a fixed number of Lloyd iterations for all subspaces at
once — this is the shape a TPU offline-coding job wants (one big einsum per
iteration instead of M small ones).

Empty clusters are re-seeded from the point currently farthest from its
centroid, the standard production fix.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared L2 between rows of x (n,d) and c (k,d) -> (n,k)."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    xc = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)


def _kmeanspp_init(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding for one subspace: x (n, d) -> (k, d)."""
    n = x.shape[0]
    key0, key_loop = jax.random.split(key)
    first = jax.random.randint(key0, (), 0, n)
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    mind = _sq_dists(x, x[first][None, :])[:, 0]

    def body(i, carry):
        centroids, mind, key = carry
        key, sub = jax.random.split(key)
        # Sample proportional to squared distance (k-means++).
        probs = mind / jnp.maximum(jnp.sum(mind), 1e-30)
        idx = jax.random.choice(sub, n, p=probs)
        c_new = x[idx]
        centroids = centroids.at[i].set(c_new)
        d_new = jnp.sum((x - c_new[None, :]) ** 2, axis=-1)
        mind = jnp.minimum(mind, d_new)
        return centroids, mind, key

    centroids, _, _ = jax.lax.fori_loop(1, k, body, (centroids, mind, key_loop))
    return centroids


def _lloyd_step(x: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One Lloyd iteration for one subspace. Returns (new_centroids, inertia)."""
    d2 = _sq_dists(x, centroids)
    assign = jnp.argmin(d2, axis=-1)
    inertia = jnp.sum(jnp.min(d2, axis=-1))
    k = centroids.shape[0]
    one_hot = jax.nn.one_hot(assign, k, dtype=x.dtype)  # (n, k)
    counts = jnp.sum(one_hot, axis=0)  # (k,)
    sums = jnp.matmul(one_hot.T, x, precision=jax.lax.Precision.HIGHEST)  # (k, d)
    new = sums / jnp.maximum(counts[:, None], 1.0)
    # Keep old centroid where the cluster went empty, then re-seed it from the
    # farthest point.
    empty = counts < 0.5
    new = jnp.where(empty[:, None], centroids, new)
    far = jnp.argmax(jnp.min(d2, axis=-1))
    # re-seed at most one empty cluster per iteration (cheap and sufficient)
    first_empty = jnp.argmax(empty)
    any_empty = jnp.any(empty)
    new = jax.lax.cond(
        any_empty,
        lambda nc: nc.at[first_empty].set(x[far]),
        lambda nc: nc,
        new,
    )
    return new, inertia


@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_fit(key: jax.Array, x: jax.Array, *, k: int, iters: int = 25):
    """k-means over one space: x (n, d) -> centroids (k, d), inertia ()."""

    centroids = _kmeanspp_init(key, x, k)

    def body(_, c):
        new, _ = _lloyd_step(x, c)
        return new

    centroids = jax.lax.fori_loop(0, iters, body, centroids)
    _, inertia = _lloyd_step(x, centroids)
    return centroids, inertia


@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_fit_batched(key: jax.Array, xs: jax.Array, *, k: int, iters: int = 25):
    """Batched k-means: xs (M, n, ds) -> centroids (M, k, ds), inertias (M,).

    One jitted program fits all M subspace codebooks simultaneously (vmap over
    the subspace axis), the TPU-friendly layout for Flash/PQ codebook training.
    """
    m = xs.shape[0]
    keys = jax.random.split(key, m)
    fit = lambda kk, xx: kmeans_fit(kk, xx, k=k, iters=iters)
    return jax.vmap(fit)(keys, xs)


def assign_codes(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest-centroid assignment (paper Eq. 8): x (n,d), centroids (k,d) -> (n,) int32."""
    return jnp.argmin(_sq_dists(x, centroids), axis=-1).astype(jnp.int32)


def assign_codes_batched(xs: jax.Array, centroids: jax.Array) -> jax.Array:
    """xs (M, n, ds), centroids (M, k, ds) -> (M, n) int32."""
    return jax.vmap(assign_codes)(xs, centroids)
