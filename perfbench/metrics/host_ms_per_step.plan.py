"""Host milliseconds per engine step in the ``plan`` phase of the step
loop: the scheduler's plan and the step's host operand arrays (lanes,
block tables, sampling rows).
The program's ``host_s_plan`` counter over its ``steps`` counter."""

COUNTER = "host_s_plan"


def read(run):
    if COUNTER not in run.after or run.delta("steps") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("steps")
