"""Shared fixtures: small clustered datasets (embedding-like) + helpers.

NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device; only
launch/dryrun.py forces 512 placeholder devices (in its own process).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def make_clustered(
    n: int, d: int, *, n_clusters: int = 24, sep: float = 1.0, seed: int = 0
) -> np.ndarray:
    """Gaussian-mixture data with smooth variance decay (embedding-like)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)) * sep
    # anisotropic within-cluster noise: decaying per-dim scales, like PCA
    # spectra of real embedding sets
    scales = np.linspace(1.0, 0.2, d)
    x = centers[rng.integers(0, n_clusters, n)] + rng.normal(size=(n, d)) * scales
    return x.astype(np.float32)


@pytest.fixture(autouse=True, scope="module")
def _bounded_executable_cache():
    """Drop compiled executables at module boundaries.

    XLA-CPU's JIT segfaults inside ``backend_compile`` once one process
    holds a few hundred live compiled computations (reproducible at the
    same test ~70% through a full-suite run; every module passes alone).
    Clearing per module keeps the resident count bounded — modules pay
    their own compiles either way, only cross-module reuse is lost.
    """
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def small_data():
    """(data (2000, 48), queries (64, 48)) jnp arrays."""
    x = make_clustered(2064, 48, seed=0)
    return jnp.asarray(x[:2000]), jnp.asarray(x[2000:])


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
