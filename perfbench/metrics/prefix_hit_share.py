"""Share of the window's prefix-cache lookups that hit (%), from the
program's ``prefix_hits`` and ``prefix_misses`` counters."""


def read(run):
    hits, misses = run.delta("prefix_hits"), run.delta("prefix_misses")
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
