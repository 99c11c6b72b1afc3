"""Most pages retired but not yet reclaimed by the SMR scheme at any sample
of the window (the program's ``pool_awaiting_reclaim`` counter, sampled
every quarter second)."""


def read(run):
    if not run.samples:
        return None
    return float(max(s["pool_awaiting_reclaim"] for s in run.samples))
