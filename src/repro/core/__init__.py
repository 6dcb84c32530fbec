"""Core — the paper's contribution: compact coding for graph indexing.

Public surface:
    fit_flash / FlashCoder / query_ctx / adc_lookup / sdc_lookup   (§3.3 Flash)
    fit_pq / fit_sq / fit_pca_coder                                 (§3.2 baselines)
    hyperplane_margin / error_term / calibrate                      (§3.1 theory)
"""

from repro.core.baselines import (  # noqa: F401
    PCACoder,
    PQCoder,
    SQCoder,
    fit_pca_coder,
    fit_pq,
    fit_sq,
    pca_dist,
    pca_encode,
    pca_reconstruct,
    pq_adc_table,
    pq_encode,
    pq_reconstruct,
    pq_sdc_lookup,
    sq_dist,
    sq_encode,
    sq_reconstruct,
)
from repro.core.flash import (  # noqa: F401
    FlashCoder,
    FlashQueryCtx,
    adc_lookup,
    encode,
    estimate_distance,
    fit_flash,
    from_neighbor_blocks,
    pack_codes,
    query_ctx,
    reconstruct,
    sdc_lookup,
    sdc_table,
    to_neighbor_blocks,
    unpack_codes,
)
from repro.core.margin import (  # noqa: F401
    TripleSet,
    calibrate,
    comparison_sign,
    error_term,
    hyperplane_margin,
    margin_satisfaction_rate,
    sample_triples,
)
from repro.core.pca import PCAModel, fit_pca, transform, variance_dim  # noqa: F401
from repro.core.quantize import (  # noqa: F401
    SQParams,
    TableQuant,
    dequantize_table,
    fit_table_quant,
    pack4,
    quantize_table,
    sq_decode,
    sq_fit,
    unpack4,
)
