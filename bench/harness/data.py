"""Seeded vector collections, made on the device in one jitted call.

A run's inputs, the collection and held-out queries from the same
mixture, are drawn from the run's seed folded with the configuration's
``data_seed``: each seed makes another collection of the same size and
shape, and the same seed makes the same one.

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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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


@functools.partial(
    jax.jit, static_argnames=("n", "d", "n_clusters", "normalize", "center_scaled")
)
def _mixture(key, scales, sep, *, n, d, n_clusters, normalize, center_scaled):
    k_c, k_a, k_x = jax.random.split(key, 3)
    centers = jax.random.normal(k_c, (n_clusters, d), jnp.float32) * sep
    if center_scaled:
        centers = centers * scales
    assign = jax.random.randint(k_a, (n,), 0, n_clusters)
    x = centers[assign] + jax.random.normal(k_x, (n, d), jnp.float32) * scales
    if normalize:
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x


def collection(seed: int, n: int, gen: dict, d: int, *, salt: int = 0) -> jax.Array:
    """(n, d) float32 on the default device, the same for the same seed
    and ``salt`` (a configuration's ``data_seed``, so that two
    configurations draw apart on one seed)."""
    if gen.get("kind", "gmm") != "gmm":
        raise ValueError(f"unknown generator kind {gen.get('kind')!r}")
    return _mixture(
        jax.random.fold_in(seed_key(seed), salt), jnp.asarray(noise_scales(d, gen)),
        jnp.float32(gen.get("sep", 1.0)),
        n=int(n), d=int(d), n_clusters=int(gen["n_clusters"]),
        normalize=bool(gen.get("normalize", False)),
        center_scaled=bool(gen.get("center_scaled", False)),
    )


def inputs(cfg: dict, seed: int, n_queries: int) -> tuple[np.ndarray, np.ndarray]:
    """Host copies of the ``n`` base rows and ``n_queries`` held-out rows
    of the collection that ``seed`` draws for this configuration."""
    n = int(cfg["n"])
    x = collection(seed, n + n_queries, cfg["generator"], int(cfg["dim"]),
                   salt=int(cfg["data_seed"]))
    return np.asarray(x[:n]), np.asarray(x[n:])
