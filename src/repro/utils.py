"""Small shared utilities: PRNG helpers, tree math, timing, padding."""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def tree_size(tree: Pytree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: Pytree) -> int:
    """Total bytes across all leaves."""
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_global_norm(tree: Pytree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to the next multiple of ``b``."""
    return ceil_div(a, b) * b


def pad_to(x: jax.Array, size: int, axis: int = 0, value=0) -> jax.Array:
    """Pad ``x`` along ``axis`` up to ``size`` with ``value``."""
    cur = x.shape[axis]
    if cur == size:
        return x
    if cur > size:
        raise ValueError(f"cannot pad axis {axis} of length {cur} down to {size}")
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - cur)
    return jnp.pad(x, widths, constant_values=value)


def use_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry-point script.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only setting: JAX reads
    it itself. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of what a later run must find
    again. Scripts call this; the library and the tests never do.
    Returns the cache directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextmanager
def timed(label: str, sink: dict | None = None) -> Iterator[None]:
    """Wall-clock a block; append seconds into ``sink[label]`` if given."""
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if sink is not None:
        sink.setdefault(label, []).append(dt)


def block_until_ready(tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        tree,
    )


def fingerprint(tree: Pytree) -> float:
    """Cheap deterministic scalar fingerprint of a pytree (for checkpoint checks)."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf)
        if arr.dtype.kind in "fc":
            total += float(np.sum(np.nan_to_num(arr, posinf=1e30, neginf=-1e30)))
        else:
            total += float(np.sum(arr.astype(np.int64) % 1000003))
    return total
