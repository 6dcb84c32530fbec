"""Compile rehearsals: every Pallas kernel, and the build's commit program,
compiled for a described TPU v5e.

Interpret-mode parity (test_kernels.py, test_expand.py) cannot see what the
TPU compiler refuses: blocks not aligned to the (8, 128) tiling, reductions
Mosaic cannot lay out, operand layouts that force XLA to copy a whole array
before the kernel. These tests compile each kernel at the widths of a
million-vector deployment (n = 10⁶, R = 32, M = 16, K = 16, W = 8, C = 96,
d = 768) for one chip of a described ``v5e:2x2`` topology, with
``interpret=False``. Nothing runs; the compiler is the oracle.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a test file that
loads it while being collected would break every other collecting worker.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import graph
from repro.graph.engine import BuildEngine, BuildParams, bulk_commit
from repro.kernels import ops

N, R, M, K, W, C, D = 1_000_000, 32, 16, 16, 8, 96, 768
ROUND_CHUNK = 256  # vertices per flash_round launch (graph/engine.py)
Q = 32  # queries per served batch


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return compiled


def _assert_no_relayout(compiled, *big):
    """A kernel operand in a layout Mosaic does not accept makes XLA copy the
    whole array before every call; at these widths that is GBs of temp."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(big) // 4, f"{temp} B of temp: an operand was relaid"


def test_flash_round(one_chip):
    _compile(
        one_chip,
        lambda c, a: ops.flash_round(c, a, impl="pallas"),
        ((ROUND_CHUNK, C, M), jnp.int32),
        ((ROUND_CHUNK, M, K), jnp.int32),
    )


@pytest.mark.parametrize(
    "mirror", [((N, R, M // 2), jnp.uint8), ((N, R, M), jnp.int32)],
    ids=["packed", "int32_mirror"],
)
def test_flash_expand(one_chip, mirror):
    compiled = _compile(
        one_chip,
        lambda nd, adj, mir, adt: ops.flash_expand(nd, adj, mir, adt, impl="pallas"),
        ((W,), jnp.int32),
        ((N, R), jnp.int32),
        mirror,
        ((M, K), jnp.int32),
    )
    _assert_no_relayout(compiled, N * R * 4)


def test_flash_expand_vmapped(one_chip):
    """The served path vmaps the beam over a query batch."""
    fn = jax.vmap(
        lambda nd, adj, mir, adt: ops.flash_expand(nd, adj, mir, adt, impl="pallas"),
        in_axes=(0, None, None, 0),
    )
    compiled = _compile(
        one_chip, fn,
        ((Q, W), jnp.int32),
        ((N, R), jnp.int32),
        ((N, R, M // 2), jnp.uint8),
        ((Q, M, K), jnp.int32),
    )
    _assert_no_relayout(compiled, N * R * 4)


@pytest.mark.parametrize("w", [1, 4, 8])
def test_flash_scan_batch_vmapped(one_chip, w):
    fn = jax.vmap(lambda rows, adt: ops.flash_scan_batch(rows, adt, impl="pallas"))
    _compile(one_chip, fn, ((Q, w, R, M), jnp.int32), ((Q, M, K), jnp.int32))


def test_flash_scan(one_chip):
    _compile(
        one_chip,
        lambda c, a: ops.flash_scan(c, a, impl="pallas"),
        ((N, M), jnp.int32),
        ((M, K), jnp.int32),
    )


def test_l2_batch(one_chip):
    _compile(
        one_chip,
        lambda x, y: ops.l2_batch(x, y, impl="pallas"),
        ((4096, D), jnp.float32),
        ((4096, D), jnp.float32),
    )


def test_nearest_centroid(one_chip):
    _compile(
        one_chip,
        lambda x, c: ops.nearest_centroid(x, c, impl="pallas"),
        ((65536, D), jnp.float32),
        ((64, D), jnp.float32),
    )


def test_sq_l2(one_chip):
    _compile(
        one_chip,
        lambda q, db, s2: ops.sq_l2(q, db, s2, impl="pallas"),
        ((D,), jnp.int32),
        ((N, D), jnp.int32),
        ((D,), jnp.float32),
    )


def test_bulk_commit_applies_no_permutation_by_gather(one_chip):
    """Every sort in the layer-0 commit carries its ids and distances: the
    compiled program holds no ``take_along_axis`` and no ``top_k``, whose
    element gathers a TPU runs about one element at a time."""
    n, pool, r = 1024, 96, 32  # deep-96's layer-0 widths, coder M=48
    x = np.random.default_rng(0).normal(size=(n, 96)).astype(np.float32)
    be = graph.make_backend(
        "flash_blocked", x, d_f=96, m_f=48, kmeans_iters=1, r_for_blocked=r
    )
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = bulk_commit.lower(
        BuildEngine(BuildParams()),
        s((n, r), jnp.int32), s((n, r), jnp.float32),
        jax.tree.map(lambda a: s(a.shape, a.dtype), be),
        s((n,), jnp.int32), s((n, pool), jnp.int32), s((n, pool), jnp.float32),
        r=r,
    ).compile().as_text()
    for op in ("take_along_axis", "top_k", "argsort"):
        assert op not in text, f"{op} in the compiled bulk_commit"
