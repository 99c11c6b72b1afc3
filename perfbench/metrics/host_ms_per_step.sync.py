"""Host milliseconds per engine step in the ``sync`` phase of the step
loop: waiting for the step's outputs and copying them to the host
(spans ``engine.sync.wait`` and ``engine.sync.copy``).
The program's ``host_s_sync`` counter over its ``steps`` counter."""

COUNTER = "host_s_sync"


def read(run):
    if COUNTER not in run.after or run.delta("steps") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("steps")
