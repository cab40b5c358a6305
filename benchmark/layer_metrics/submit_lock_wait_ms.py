"""Mean wait of a submitting thread for the engine's lock, per ``submit``
of the window: ``stats()["submit"]``, the same two stamps as the ``lock``
leg of the request's phase ledger."""

from _common import delta


def read(run):
    n, wait = delta(run, "submit", "n"), delta(run, "submit", "lock_wait_s")
    if not n or wait is None:
        return None
    return 1e3 * wait / n
