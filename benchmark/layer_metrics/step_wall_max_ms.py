"""The longest engine step of the window, as the upper bound of the
highest bucket of ``stats()["loop"]["step_wall_hist"]`` that gained a count
between the window's edges (bounds a factor of √2 apart; the overflow
bucket counts as one more factor): says whether ANY step took seconds,
which a percentile of 10,000 gaps cannot."""

import math


def read(run):
    c = run.get("counters")
    try:
        loop = c["close"]["loop"]
        gained = [a - b for a, b in zip(loop["step_wall_hist"], c["open"]["loop"]["step_wall_hist"])]
    except (KeyError, TypeError):
        return None  # an older replica keeps no such histogram
    top = max((i for i, n in enumerate(gained) if n > 0), default=None)
    if top is None:
        return None
    bounds = loop["step_wall_bounds_s"]
    return 1e3 * (bounds[top] if top < len(bounds) else bounds[-1] * math.sqrt(2.0))
