"""Host milliseconds per engine step in the ``dispatch`` phase of the step
loop: the jitted step calls, their operand transfers included, until
they return.
The program's ``host_s_dispatch`` counter over its ``steps`` counter."""

COUNTER = "host_s_dispatch"


def read(run):
    if COUNTER not in run.after or run.delta("steps") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("steps")
