"""Packed-prefill attention kernel (``kernels/packed_prefill.py``) against
its roofline (%): the least time the chip needs for the prompt positions
prefilled in the window (their causal attention operations, their context's
K and V read once), over the kernel's device time in the trace.  Decode
tokens that ride a packed chunk are work the kernel does beyond this count.
"""

from perfbench import counts

KERNEL = "packed_prefill"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s(KERNEL)
    spans = run.prefilled()
    if t <= 0 or not spans:
        return None
    flops = sum(counts.attention_flops(run.dims, a, b) for a, b in spans)
    nbytes = sum(counts.prefill_attention_bytes(run.dims, a, b)
                 for a, b in spans)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
