"""Roofline share of the bulk refinement-round kernel (``flash_round``,
``kernels/flash_scan.py``) in one traced build, in %.

For each call, what the algorithm needs, counted from the call's output
shape (vertices × candidates) and the configuration's coder: each distance
is M lookup-adds over M codes of l_f bits, each vertex reads its own table
of M × 2^l_f entries of h bits, and each distance is written as 4 bytes.
The least time is the larger of ops over the int8 peak and bytes over the
HBM peak; the share is the least time summed over calls over the kernel's
device time. The count does not read the kernel's operands, so it is the
same whatever layout a kernel uses."""

from harness import trace

KERNEL = "flash_round"


def read(r):
    calls = trace.kernel_calls(r.record, KERNEL)
    if not calls or not r.peaks:
        return None
    coder = r.config["index"]["coder"]
    m, l_f, h = coder["m_f"], coder.get("l_f", 4), coder.get("h", 8)
    least = spent = 0.0
    for dur, text in calls:
        dims = trace.out_dims(text)
        if len(dims) < 2:
            return None
        dists = 1
        for x in dims:
            dists *= x
        vertices = dists // dims[-1]
        ops = dists * m
        nbytes = dists * m * l_f / 8 + vertices * m * 2**l_f * h / 8 + dists * 4
        least += max(ops / r.peaks["int8_ops"], nbytes / r.peaks["hbm_bytes_per_s"])
        spent += dur / 1e9
    return 100.0 * least / spent if spent > 0 else None
