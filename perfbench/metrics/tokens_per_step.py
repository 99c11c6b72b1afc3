"""Output tokens emitted in the window per engine step (the program's
``steps`` counter): how full the step loop keeps each device call."""


def read(run):
    steps = run.delta("steps")
    if steps <= 0:
        return None
    return run.emitted() / steps
