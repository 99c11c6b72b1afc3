"""90th percentile of time to first token (ms) over every request due in
an open-loop window, measured from when it was due.  Kept per-layer, not
end to end: at four fifths of the knee these windows hold too few requests
for ten samples beyond the 90th percentile, and the median already moves
too much from seed to seed (``ttft_p50_ms.open``)."""

from perfbench import harness


def read(run):
    ttft = harness.ttft_ms(run)
    return harness.percentile(ttft, 90) if ttft else None
