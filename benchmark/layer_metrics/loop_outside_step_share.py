"""Share of the window the engine's loop spent OUTSIDE a step with work:
waiting for its own lock (a submitter or a ``stats()`` reader held it) or
idle with nothing queued.  With 120 callers on 32 slots it should be near
zero; seconds of it mean the replica stood still.  The denominator is the
difference of the readings' own clocks (``t_read``, taken under the lock),
not the nominal window: a reading can wait many steps for the lock."""

from _common import delta


def read(run):
    wait, idle, dt = (delta(run, "loop", "lock_wait_s"), delta(run, "loop", "idle_s"),
                      delta(run, "t_read"))
    if wait is None or idle is None or not dt:
        return None
    return 100.0 * (wait + idle) / dt
