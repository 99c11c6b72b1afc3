"""Median time to first token (ms) over every request due in an open-loop
window, measured from when it was due.  Kept per-layer, not end to end:
with arrivals and lengths drawn from the seed, the median of a window's
18 requests moves 13-30 % from seed to seed, more than any bound allows."""

from perfbench import harness


def read(run):
    ttft = harness.ttft_ms(run)
    return harness.percentile(ttft, 50) if ttft else None
