"""Small helpers the readers share.  A reader takes the run's sources (a
dict: ``records``, ``counters`` at the window's edges, the ``reduced``
trace, the ``train`` loop's own report, ``peaks``, ``model``, ``engine``)
and returns a number, or None when there is nothing to read."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402,F401
from benchmark.harness import family_piece, median  # noqa: E402,F401


def delta(run, *path):
    """Counter at the window's close minus at its opening."""
    if run.get("counters") is None:
        return None

    def dig(d):
        for p in path:
            d = d[p]
        return d

    try:
        return dig(run["counters"]["close"]) - dig(run["counters"]["open"])
    except KeyError:
        return None
