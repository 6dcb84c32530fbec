"""Pallas TPU kernels for the paper's compute hot-spots.

Kernels (each <name>.py has a pl.pallas_call + explicit BlockSpec VMEM tiling;
ref.py holds the pure-jnp oracle; ops.py the jit'd dispatching wrappers):

  flash_scan   — ADT lookup-accumulate (the CPU `pshufb` analogue, paper
                 §3.3.5) over the blocked code layout (§3.3.4); the flat
                 scan and the bulk refinement-round scan (DESIGN.md §12,
                 one table per row) are the same kernel.
  flash_expand — one fused beam-expansion step (DESIGN.md §10): scalar-
                 prefetched in-kernel gather of adjacency + packed 4-bit
                 code tiles, nibble lookup against the even/odd ADT rows.
  l2_batch     — tiled ‖x‖²+‖y‖²−2x·yᵀ distance matrix on the MXU
                 (full-precision baseline path + k-means training).
  sq_l2        — int-domain scaled L2 for the optimized HNSW-SQ baseline.

Every ``*_pallas`` entry point takes a required ``interpret`` flag.
"""

from repro.kernels import ops, ref  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    flash_expand,
    flash_round,
    flash_scan,
    flash_scan_blocked,
    l2_batch,
    set_default_impl,
    sq_l2,
)
