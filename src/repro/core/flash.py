"""Flash — the paper's compact coding strategy (§3.3).

Pipeline (fit):
  1. PCA-rotate the space, keep the first ``d_F`` principal dims (§3.3.2).
  2. Split into ``M_F`` subspaces; k-means codebook of ``K = 2^{L_F}``
     centroids each (§3.3.3, Eq. 8). ``L_F = 4`` → K = 16 so one subspace's
     asymmetric distance table (ADT) occupies 16 × H bits = 128 bits at H=8 —
     exactly one CPU SIMD register; on TPU the full (M_F, K) ADT is
     VMEM/VREG-resident (see DESIGN.md §2).
  3. Precompute symmetric distance tables (SDT, (M_F, K, K)) of inter-centroid
     partial distances, shared by every insertion (§3.3.3).
  4. Quantize ADT and SDT entries to H-bit levels with a *shared* (dist_min, Δ)
     (Eq. 9) so CA-stage (ADT) and NS-stage (SDT) values are mutually
     comparable.

Per inserted/queried vector: ``query_ctx`` builds the quantized ADT; distances
to a batch of neighbors are then ``Σ_m ADT[m, code[b, m]]`` — a gather-free
lookup-accumulate that `repro.kernels.flash_scan` implements as a Pallas TPU
kernel (this module keeps the pure-jnp form as the reference path).

Everything in :class:`FlashCoder` is a pytree of arrays, so coders can be
donated to jitted build/search programs and sharded like any other state.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import kmeans as km
from repro.core import pca as pca_mod
from repro.core import quantize as qz

#: f32 matmuls at full precision: a TPU's default runs them as one bf16
#: pass, an error as large as the small partial distances that order near
#: neighbours
_EXACT = jax.lax.Precision.HIGHEST


class FlashCoder(NamedTuple):
    """Fitted Flash coding state (a pytree; static hyperparams via shapes).

    mean:      (D,)        PCA mean.
    rot:       (D, d_F)    truncated PCA rotation (columns orthonormal).
    codebooks: (M, K, ds)  per-subspace centroids in PCA domain
                           (d_F padded to M*ds with zeros).
    sdt_q:     (M, K, K)   quantized symmetric tables (int32 levels, [0, 2^H)).
    dist_min:  ()          shared table-quantization floor (Eq. 9).
    delta:     ()          shared table-quantization range (Eq. 9).
    h_bits:    ()          H — bits per quantized table entry.
    """

    mean: jax.Array
    rot: jax.Array
    codebooks: jax.Array
    sdt_q: jax.Array
    dist_min: jax.Array
    delta: jax.Array
    h_bits: jax.Array

    # ---- static-shape helpers -------------------------------------------------
    @property
    def d_in(self) -> int:
        return self.rot.shape[0]

    @property
    def d_f(self) -> int:
        return self.rot.shape[1]

    @property
    def m_f(self) -> int:
        return self.codebooks.shape[0]

    @property
    def k(self) -> int:
        return self.codebooks.shape[1]

    @property
    def ds(self) -> int:
        return self.codebooks.shape[2]

    @property
    def code_bytes(self) -> float:
        """HBM bytes per encoded vector (4-bit packed, as on CPU)."""
        l_f = int(np.log2(self.k))
        return self.m_f * l_f / 8.0


class FlashQueryCtx(NamedTuple):
    """Per-inserted-vector state: the register-resident ADT (§3.3.3).

    adt_q: (M, K) int32 — quantized partial distances (Eq. 9 levels).
    adt_f: (M, K) f32   — unquantized partials (search-time rerank ordering).
    codes: (M,)  int32  — the vector's own codewords (for SDT comparisons).
    """

    adt_q: jax.Array
    adt_f: jax.Array
    codes: jax.Array


def _split_subspaces(z: jax.Array, m: int, ds: int) -> jax.Array:
    """(n, d_F) -> (m, n, ds), zero-padding d_F up to m*ds."""
    n, d = z.shape
    pad = m * ds - d
    if pad:
        z = jnp.pad(z, ((0, 0), (0, pad)))
    return jnp.transpose(z.reshape(n, m, ds), (1, 0, 2))


def fit_flash(
    key: jax.Array,
    sample: jax.Array,
    *,
    d_f: int,
    m_f: int,
    l_f: int = 4,
    h: int = 8,
    kmeans_iters: int = 25,
    max_fit_sample: int = 32768,
) -> FlashCoder:
    """Fit Flash on a training sample (n, D).

    ``d_f`` — principal dims kept; ``m_f`` — subspaces; ``l_f`` — bits per
    codeword (K = 2^l_f centroids); ``h`` — bits per quantized table entry.
    """
    sample = jnp.asarray(sample, jnp.float32)
    n, d_in = sample.shape
    if d_f > d_in:
        raise ValueError(f"d_f={d_f} exceeds input dim {d_in}")
    k = 1 << l_f
    ds = -(-d_f // m_f)  # ceil

    with obs.span("build/coder/pca", d_in=d_in):
        # the host copy, the float64 covariance and its eigh
        model = pca_mod.fit_pca(sample, max_sample=max_fit_sample)
        # Balance variance across subspaces: principal dims are assigned
        # round-robin (subspace m gets dims m, m+M, m+2M, …). With contiguous
        # chunks the first subspace would dominate the shared (dist_min, Δ)
        # quantization range (Eq. 9) and starve the rest of the 2^H levels —
        # this is the "bit utilization" co-design of §3.3.2/§3.3.3. The
        # permutation (and zero-padding of d_F up to M·ds) is folded into the
        # rotation, so encode/query pay no runtime cost.
        d_pad = m_f * ds
        rot_np = np.zeros((d_in, d_pad), np.float32)
        rot_np[:, :d_f] = np.asarray(model.components[:, :d_f])
        perm = np.concatenate([np.arange(m, d_pad, m_f) for m in range(m_f)])
        rot = jnp.asarray(rot_np[:, perm])
        mean = model.mean

    with obs.span("build/coder/kmeans", k=k, iters=kmeans_iters):
        fit_rows = min(n, max_fit_sample)
        z = jnp.matmul(sample[:fit_rows] - mean, rot, precision=_EXACT)  # (n', d_F)
        subs = _split_subspaces(z, m_f, ds)  # (M, n', ds)

        codebooks, _ = km.kmeans_fit_batched(key, subs, k=k, iters=kmeans_iters)

        # Symmetric tables: inter-centroid squared partial distances.
        diff = codebooks[:, :, None, :] - codebooks[:, None, :, :]  # (M, K, K, ds)
        sdt_f = jnp.sum(diff * diff, axis=-1)  # (M, K, K)

        # Shared quantizer calibration (§3.3.3): per-subspace [min,max] over both
        # sample-to-centroid (ADT-like) and centroid-to-centroid (SDT) partials.
        d_sample = _partial_dists(subs, codebooks)  # (M, n', K)
        per_min = jnp.minimum(
            jnp.min(d_sample, axis=(1, 2)), jnp.min(sdt_f, axis=(1, 2))
        )
        per_max = jnp.maximum(
            jnp.max(d_sample, axis=(1, 2)), jnp.max(sdt_f, axis=(1, 2))
        )
        tq = qz.fit_table_quant(per_min, per_max, h=h)
        sdt_q = qz.quantize_table(tq, sdt_f)

    return FlashCoder(
        mean=mean,
        rot=rot,
        codebooks=codebooks,
        sdt_q=sdt_q,
        dist_min=tq.dist_min,
        delta=tq.delta,
        h_bits=tq.h,
    )


def _partial_dists(subs: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(M, n, ds) vs (M, K, ds) -> per-subspace squared dists (M, n, K)."""
    x2 = jnp.sum(subs * subs, axis=-1, keepdims=True)  # (M, n, 1)
    c2 = jnp.sum(codebooks * codebooks, axis=-1)  # (M, K)
    xc = jnp.einsum("mnd,mkd->mnk", subs, codebooks, precision=_EXACT)
    return jnp.maximum(x2 + c2[:, None, :] - 2.0 * xc, 0.0)


def encode(coder: FlashCoder, x: jax.Array) -> jax.Array:
    """Encode vectors (n, D) -> codewords (n, M) int32 in [0, K)."""
    z = jnp.matmul(x - coder.mean, coder.rot, precision=_EXACT)
    subs = _split_subspaces(z, coder.m_f, coder.ds)  # (M, n, ds)
    codes = km.assign_codes_batched(subs, coder.codebooks)  # (M, n)
    return codes.T.astype(jnp.int32)


def reconstruct(coder: FlashCoder, x: jax.Array) -> jax.Array:
    """decode(encode(x)) lifted back to the original space.

    This is the "derived vector" of §3.1 used in the Theorem-1 error term:
    E_u = u − reconstruct(u).
    """
    codes = encode(coder, x)  # (n, M)
    cb = coder.codebooks  # (M, K, ds)
    m_idx = jnp.arange(coder.m_f)[:, None]
    gathered = cb[m_idx, codes.T]  # (M, n, ds)
    z_hat = jnp.transpose(gathered, (1, 0, 2)).reshape(x.shape[0], -1)
    z_hat = z_hat[:, : coder.d_f]
    return z_hat @ coder.rot.T + coder.mean


def query_ctx(coder: FlashCoder, q: jax.Array) -> FlashQueryCtx:
    """Build the per-vector ADT + own codewords (one insertion's state).

    q: (D,) — returns quantized and float ADTs of shape (M, K).
    Codeword and ADT generation share the same distance computations
    (paper Remark 2): the argmin over the ADT row *is* the codeword.
    """
    z = jnp.matmul(q - coder.mean, coder.rot, precision=_EXACT)  # (d_F,)
    subs = _split_subspaces(z[None, :], coder.m_f, coder.ds)  # (M, 1, ds)
    adt_f = _partial_dists(subs, coder.codebooks)[:, 0, :]  # (M, K)
    tq = qz.TableQuant(coder.dist_min, coder.delta, coder.h_bits)
    adt_q = qz.quantize_table(tq, adt_f)
    codes = jnp.argmin(adt_f, axis=-1).astype(jnp.int32)  # (M,)
    return FlashQueryCtx(adt_q=adt_q, adt_f=adt_f, codes=codes)


def adc_lookup(adt: jax.Array, codes: jax.Array) -> jax.Array:
    """Reference ADT scan: Σ_m adt[m, codes[..., m]].

    adt:   (M, K) int32 or f32.
    codes: (..., M) int32.
    Returns (...,) summed partial distances (int32 if adt is int).

    The production path is `repro.kernels.ops.flash_scan` (Pallas); this jnp
    form doubles as its oracle.
    """
    m_idx = jnp.arange(adt.shape[0])
    gathered = adt[m_idx, codes]  # (..., M) — fancy index broadcasts m_idx
    return jnp.sum(gathered, axis=-1)


def sdc_lookup(coder: FlashCoder, codes_a: jax.Array, codes_b: jax.Array) -> jax.Array:
    """Symmetric distance via SDT: Σ_m sdt_q[m, a_m, b_m].

    codes_a, codes_b: (..., M) int32 — broadcastable against each other.
    Used in the NS stage for candidate-to-candidate comparisons (§3.3.3);
    values share the ADT quantization scale so they compare against ADC sums.
    """
    codes_a, codes_b = jnp.broadcast_arrays(codes_a, codes_b)
    m_idx = jnp.arange(coder.m_f)
    vals = coder.sdt_q[m_idx, codes_a, codes_b]  # (..., M)
    return jnp.sum(vals, axis=-1)


def sdc_table(coder: FlashCoder, codes: jax.Array) -> jax.Array:
    """All-pairs :func:`sdc_lookup` among (C, M) codes -> (C, C) f32.

    The element gather ``sdt_q[m, a_m, b_m]`` costs C²·M scalar lookups,
    which a TPU executes one by one. As one-hot contractions it runs on the
    MXU: rows[i, m, :] = sdt_q[m, a_im, :], then
    table[i, j] = Σ_{m,l} rows[i, m, l] · onehot(b_jm)[l]. The levels and
    their sums are integers below 2^24 and every product has a 0/1 factor,
    so f32 at HIGHEST precision gives the same integer sums as the gather.
    """
    onehot = jax.nn.one_hot(codes, coder.k, dtype=jnp.float32)  # (C, M, K)
    sdt = coder.sdt_q.astype(jnp.float32)
    rows = jnp.einsum("cmk,mkl->cml", onehot, sdt, precision=_EXACT)
    return jnp.einsum("iml,jml->ij", rows, onehot, precision=_EXACT)


# ---------------------------------------------------------------------------
# Packed 4-bit code storage (§3.3.3 — two codewords per byte, as on CPU)
# ---------------------------------------------------------------------------


def pack_codes(codes: jax.Array) -> jax.Array:
    """Pack codewords (…, M) int in [0, 16) into (…, ⌈M/2⌉) uint8.

    The HBM storage format of the blocked neighbor mirror: two 4-bit
    codewords per int8 lane, halving the mirror's footprint and the DMA
    bytes per beam-expansion step. Odd M is zero-padded (the high nibble of
    the last byte); :func:`unpack_codes` slices it back off. Only valid for
    K ≤ 16 coders (L_F ≤ 4, the paper's Flash configuration).
    """
    m = codes.shape[-1]
    if m % 2:
        codes = jnp.concatenate(
            [codes, jnp.zeros(codes.shape[:-1] + (1,), codes.dtype)], axis=-1
        )
    return qz.pack4(codes)


def unpack_codes(packed: jax.Array, m: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: (…, ⌈m/2⌉) uint8 -> (…, m) int32."""
    return qz.unpack4(packed)[..., :m]


# ---------------------------------------------------------------------------
# Access-aware neighbor-block layout (§3.3.4)
# ---------------------------------------------------------------------------


def to_neighbor_blocks(codes: jax.Array, b: int) -> jax.Array:
    """Re-layout neighbor codewords for batched register loads.

    codes: (R, M) — codewords of one vertex's (padded) neighbor list.
    Returns (R // b, M, b): within each block of ``b`` neighbors the codewords
    are grouped *by subspace* so one contiguous load fetches the b codewords of
    a single subspace — the layout of Figure 5 (lower right). R must be a
    multiple of b (pad with code 0 / id −1 upstream).
    """
    r, m = codes.shape
    if r % b:
        raise ValueError(f"R={r} not a multiple of block size b={b}")
    return jnp.transpose(codes.reshape(r // b, b, m), (0, 2, 1))


def from_neighbor_blocks(blocks: jax.Array) -> jax.Array:
    """Inverse of :func:`to_neighbor_blocks`: (nb, M, b) -> (nb*b, M)."""
    nb, m, b = blocks.shape
    return jnp.transpose(blocks, (0, 2, 1)).reshape(nb * b, m)


def estimate_distance(coder: FlashCoder, q_sum: jax.Array) -> jax.Array:
    """Map an ADC level-sum back to an approximate squared distance.

    Useful for rerank thresholds / diagnostics; comparisons never need it.
    """
    levels = (2 ** coder.h_bits - 1).astype(jnp.float32)
    m = jnp.asarray(coder.m_f, jnp.float32)
    return q_sum.astype(jnp.float32) / levels * coder.delta + m * coder.dist_min
