"""What the six station readers share (``layer_metrics/{emit,sent,acked,
written}_itl_p95_ms.py``, ``stream_wake_p95_ms.py``, ``head_hold_p95_ms.py``):
the 95th percentile of one histogram of ``stats()["stream"]`` over the
window.

The replica keeps, for each station of a streamed token's way (the loop's
emit, the handler thread's send, the ack's return, the proxy's write) the
gaps between one stream's tokens, and for two legs (``wake``, ``head_hold``)
a duration, as cumulative bucket counts on one list of upper bounds
(``bounds_s``; the last slot is the overflow).  A reader differences the
vectors of the window's two edges and interpolates inside the bucket that
holds the rank, as Prometheus' ``histogram_quantile`` does; with bounds
0.5 ms apart under 64 ms that stands within 0.25 ms of the sample's own.
A replica that keeps no such section (the parent's), or a window that
gained under ``MIN_OBSERVATIONS``, gives None.
"""

MIN_OBSERVATIONS = 100


def window_counts(run: dict, key: str):
    """(bounds_s, per-bucket counts gained between the window's edges), or
    None where either edge has no ``stream`` section or no such key."""
    c = run.get("counters")
    try:
        close, opened = c["close"]["stream"], c["open"]["stream"]
        bounds, hi, lo = close["bounds_s"], close[key], opened[key]
    except (KeyError, TypeError):
        return None
    if len(hi) != len(bounds) + 1 or len(lo) != len(hi):
        return None
    return bounds, [a - b for a, b in zip(hi, lo)]


def quantile_s(bounds, counts, q: float):
    """The ``q`` quantile in seconds: linear inside the bucket that holds
    the rank; a rank in the overflow bucket reads the highest bound."""
    total = sum(counts)
    if total <= 0:
        return None
    rank, cum, lo = q * total, 0.0, 0.0
    for bound, n in zip(bounds, counts):
        if n > 0 and cum + n >= rank:
            return lo + (bound - lo) * max(0.0, min(1.0, (rank - cum) / n))
        cum, lo = cum + n, bound
    return float(bounds[-1])


def p95_ms(run: dict, key: str):
    got = window_counts(run, key)
    if got is None or sum(got[1]) < MIN_OBSERVATIONS:
        return None
    value = quantile_s(got[0], got[1], 0.95)
    return None if value is None else 1e3 * value
