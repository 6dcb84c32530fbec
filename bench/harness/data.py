"""Seeded vector collections, made on the device in one jitted call.

A run's inputs, the collection and its held-out queries, are drawn from
the run's seed folded with the configuration's ``data_seed``: each seed
makes another collection of the same size and shape, and the same seed
makes the same one. The queries come from the same mixture as the
collection, or, where the configuration has a ``queries`` block, from a
distribution of their own around the same centres (out-of-distribution
queries, such as text queries over image embeddings).

A configuration's ``generator`` block names the shape of its data: a
Gaussian mixture whose per-dimension noise scales follow a spectrum,
optionally unit-normalised (cosine collections, where L2 order equals
cosine order). The linear spectrum with ``n_clusters=48, sep=1.0`` is the
mixture the repository's DEEP-shaped tier draws (``vector_dataset``); this
copy draws it with ``jax.random`` on the device so that set-up does not
pay for hundreds of millions of host normals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SPECTRA = ("linear", "power")
#: the keys of a configuration's ``queries`` block
QUERY_KEYS = ("spectrum", "scale_hi", "scale_lo", "offset", "normalize")
#: folded into a collection's key for its out-of-distribution queries, so
#: that they are drawn apart from the base rows
QUERY_SALT = 0x51


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _key(seed: int, salt: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), salt)


def noise_scales(d: int, gen: dict) -> np.ndarray:
    """Per-dimension standard deviations of the mixture's noise."""
    hi, lo = float(gen.get("scale_hi", 1.0)), float(gen.get("scale_lo", 0.2))
    kind = gen.get("spectrum", "linear")
    if kind == "linear":
        return np.linspace(hi, lo, d, dtype=np.float32)
    if kind == "power":
        # variance falling as i^-alpha from hi² at the first dim to lo² at d
        alpha = 2.0 * np.log(hi / lo) / np.log(d)
        i = np.arange(1, d + 1, dtype=np.float64)
        return (hi * i ** (-alpha / 2.0)).astype(np.float32)
    raise ValueError(f"unknown spectrum {kind!r}; known: {SPECTRA}")


def _centers(key, scales, sep, *, d, n_clusters, center_scaled):
    """The mixture's centres, from the first of the collection key's three
    splits."""
    k_c = jax.random.split(key, 3)[0]
    centers = jax.random.normal(k_c, (n_clusters, d), jnp.float32) * sep
    return centers * scales if center_scaled else centers


def _around(centers, k_a, k_x, scales, *, n):
    """``n`` rows, each around a centre drawn uniformly, with the noise's
    per-dimension ``scales``."""
    assign = jax.random.randint(k_a, (n,), 0, centers.shape[0])
    return centers[assign] + jax.random.normal(k_x, (n, centers.shape[1]), jnp.float32) * scales


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("n", "d", "n_clusters", "normalize", "center_scaled")
)
def _mixture(key, scales, sep, *, n, d, n_clusters, normalize, center_scaled):
    _, k_a, k_x = jax.random.split(key, 3)
    centers = _centers(key, scales, sep, d=d, n_clusters=n_clusters,
                       center_scaled=center_scaled)
    x = _around(centers, k_a, k_x, scales, n=n)
    return _unit(x) if normalize else x


@functools.partial(
    jax.jit, static_argnames=("n", "d", "n_clusters", "normalize", "center_scaled")
)
def _shifted(key, scales, sep, q_scales, offset, *, n, d, n_clusters, normalize,
             center_scaled):
    centers = _centers(key, scales, sep, d=d, n_clusters=n_clusters,
                       center_scaled=center_scaled)
    k_a, k_x, k_u = jax.random.split(jax.random.fold_in(key, QUERY_SALT), 3)
    # one shift common to every query: the gap between the two modalities
    shift = offset * _unit(jax.random.normal(k_u, (d,), jnp.float32))
    q = _around(centers, k_a, k_x, q_scales, n=n) + shift
    return _unit(q) if normalize else q


def _shape_args(gen: dict, n: int, d: int) -> dict:
    """The jitted draws' static arguments that the collection fixes."""
    if gen.get("kind", "gmm") != "gmm":
        raise ValueError(f"unknown generator kind {gen.get('kind')!r}")
    return dict(n=int(n), d=int(d), n_clusters=int(gen["n_clusters"]),
                center_scaled=bool(gen.get("center_scaled", False)))


def collection(seed: int, n: int, gen: dict, d: int, *, salt: int = 0) -> jax.Array:
    """(n, d) float32 on the default device, the same for the same seed
    and ``salt`` (a configuration's ``data_seed``, so that two
    configurations draw apart on one seed)."""
    return _mixture(
        _key(seed, salt), jnp.asarray(noise_scales(d, gen)),
        jnp.float32(gen.get("sep", 1.0)),
        normalize=bool(gen.get("normalize", False)), **_shape_args(gen, n, d),
    )


def shifted_queries(seed: int, n: int, gen: dict, block: dict, d: int, *,
                    salt: int = 0) -> jax.Array:
    """(n, d) float32 queries of the collection that ``seed``, ``gen`` and
    ``salt`` draw, from a distribution of their own: each around one of the
    collection's centres chosen uniformly, with the noise spectrum of
    ``block`` (its ``spectrum``, ``scale_hi`` and ``scale_lo``, read as
    :func:`noise_scales` reads them), shifted by ``offset`` along one unit
    direction drawn from the seed, and unit rows where it says
    ``normalize``. The collection's rows do not depend on ``n``."""
    unknown = sorted(set(block) - set(QUERY_KEYS))
    if unknown:
        raise ValueError(f"unknown keys {unknown} in a queries block; known: {QUERY_KEYS}")
    return _shifted(
        _key(seed, salt), jnp.asarray(noise_scales(d, gen)),
        jnp.float32(gen.get("sep", 1.0)), jnp.asarray(noise_scales(d, block)),
        jnp.float32(block.get("offset", 0.0)),
        normalize=bool(block.get("normalize", False)), **_shape_args(gen, n, d),
    )


def inputs(cfg: dict, seed: int, n_queries: int) -> tuple[np.ndarray, np.ndarray]:
    """Host copies of the ``n`` base rows and ``n_queries`` queries that
    ``seed`` draws for this configuration: held-out rows of the same
    collection, or, where it has a ``queries`` block, queries of their own
    distribution (:func:`shifted_queries`)."""
    n, d, gen, salt = int(cfg["n"]), int(cfg["dim"]), cfg["generator"], int(cfg["data_seed"])
    block = cfg.get("queries")
    if block is None:
        x = collection(seed, n + n_queries, gen, d, salt=salt)
        return np.asarray(x[:n]), np.asarray(x[n:])
    base = collection(seed, n, gen, d, salt=salt)
    return np.asarray(base), np.asarray(shifted_queries(seed, n_queries, gen, block, d, salt=salt))
