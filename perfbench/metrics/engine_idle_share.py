"""Share of the window the engine thread slept for want of work (%): the
program's ``host_s_idle`` counter over the window.  The cells run one
shard, so one engine thread."""

COUNTER = "host_s_idle"


def read(run):
    if COUNTER not in run.after or run.window_s <= 0:
        return None
    return 100.0 * run.delta(COUNTER) / run.window_s
