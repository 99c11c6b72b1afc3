"""Host milliseconds per engine step in the ``emit`` phase of the step
loop: tokens out, page release and SMR retire, prefix-cache insert.
The program's ``host_s_emit`` counter over its ``steps`` counter."""

COUNTER = "host_s_emit"


def read(run):
    if COUNTER not in run.after or run.delta("steps") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("steps")
