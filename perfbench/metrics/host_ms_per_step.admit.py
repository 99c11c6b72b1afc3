"""Host milliseconds per engine step in the ``admit`` phase of the step
loop: deadline sweep and admission, whose page reservation through
``BlockPool.try_alloc`` is the page pool's and the SMR scheme's host work.
The program's ``host_s_admit`` counter over its ``steps`` counter."""

COUNTER = "host_s_admit"


def read(run):
    if COUNTER not in run.after or run.delta("steps") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("steps")
