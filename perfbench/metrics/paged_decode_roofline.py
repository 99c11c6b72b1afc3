"""Split-K paged-decode attention kernel (``kernels/paged_attention.py``)
against its roofline (%): the least time the chip needs for the window's
decode queries (each over its live context, its K and V read once), over
the kernel's device time in the trace."""

from perfbench import counts

KERNEL = "paged_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s(KERNEL)
    ctxs = run.decoded()
    if t <= 0 or not ctxs:
        return None
    flops = sum(counts.attention_flops(run.dims, c - 1, c) for c in ctxs)
    nbytes = sum(counts.decode_attention_bytes(run.dims, c) for c in ctxs)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
