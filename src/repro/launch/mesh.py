"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS *before* first jax use).

Topology (TPU v5e-class):
  single pod : (16, 16)   axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

"pod" composes with "data" for batch/segment sharding (DCN-ish axis);
"model" is the fast-ICI tensor/expert/sequence axis.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the programs here place data with
    ``shard_map`` specs and index stacked outputs on the host, which
    Explicit axes (``make_mesh``'s default) would have to be annotated for."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _mesh((n // model, model), ("data", "model"))


def make_segment_mesh(n: int | None = None):
    """1-D mesh over the host's devices for segment-parallel builds.

    One "data" axis — each device (group) owns whole segments, the shape
    ``graph.sharded.ShardedBuilder`` shard_maps over. ``n`` defaults to
    every visible device; on a single-device host this returns a 1-wide
    mesh, which the builder treats as "no mesh" and falls back to the
    pool/inline path (the graceful degradation contract)."""
    devs = jax.devices()
    if n is None:
        n = len(devs)
    if not 1 <= n <= len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return _mesh((n,), ("data",), devices=devs[:n])


def batch_axes(mesh) -> tuple[str, ...]:
    """The axes a global batch shards over (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_devices(mesh) -> int:
    from repro.distributed.context import device_count

    return device_count(mesh)
