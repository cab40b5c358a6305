"""Seconds the process on the chip spent making programs, as jax times
them: tracing + lowering + the backend's compile or, where the persistent
cache hit, its load (``compile_cache.stats()``).  Warm it is the cache's
load time, cold the compiler's: ``hits`` / ``requests`` stand beside it on
the ``startup_ledger`` line.  ``retraces`` 0 is part of ``correct``, so
every compile of a run lies in its set-up."""

from _startup_ledger import ledger


def read(run):
    if ledger(run) is None:
        return None
    cc = run["device_report"].get("compile_cache") or {}
    parts = [cc.get(k) for k in ("trace_s", "lower_s", "backend_compile_s")]
    return None if None in parts else sum(parts)
