"""Preemptions inside the window per 100 requests attempted."""

from _common import delta


def read(run):
    pre = delta(run, "preemptions")
    if pre is None or not run["attempted"]:
        return None
    return 100.0 * pre / run["attempted"]
