"""Host time of a shard's step loop, by phase.

Each phase is timed on the host clock (``time.perf_counter``) and marked
with a ``jax.profiler.TraceAnnotation`` span named ``engine.<phase>`` at
the same boundary.  A profile of the serving process therefore shows what
the engine thread was doing while the device idled, and ``stats()`` keeps
the cumulative seconds whether a profiler runs or not (a span costs under
a microsecond when none does).

The phases tile the loop without overlap:

* ``idle``     the poll sleep after a step found no work;
* ``admit``    deadline sweep and admission (page reservation);
* ``plan``     the scheduler's plan and the step's host operand arrays;
* ``dispatch`` the jitted call, operand transfers included, until it returns;
* ``sync``     waiting for the step's outputs and copying them to the host;
* ``emit``     tokens out, page release and retire, prefix-cache insert.
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

PHASES = ("idle", "admit", "plan", "dispatch", "sync", "emit")


class PhaseClock:
    """Cumulative host seconds per phase of one engine thread."""

    def __init__(self):
        self.seconds = dict.fromkeys(PHASES, 0.0)

    @contextlib.contextmanager
    def __call__(self, phase: str):
        t = time.perf_counter()
        try:
            with TraceAnnotation(f"engine.{phase}"):
                yield
        finally:
            self.seconds[phase] += time.perf_counter() - t

    def stats(self) -> dict:
        """``host_s_<phase>`` for every phase."""
        return {f"host_s_{p}": s for p, s in self.seconds.items()}
