"""Mean wait in the engine's queue, enqueued under the lock until
``Scheduler.admit`` picks the request, per admission of the window
(``stats()["queue"]``; a preempted request's second wait counts again)."""

from _common import delta


def read(run):
    n, wait = delta(run, "queue", "admitted"), delta(run, "queue", "wait_s")
    if not n or wait is None:
        return None
    return 1e3 * wait / n
