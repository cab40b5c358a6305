"""What a decode of a body over latent blocks stood at, for the readers of
its scopes: the live rows and the live tokens of context a decode of the
slice had (``stats()["kv_pool"]``: ``decode_rows`` and ``decode_tokens`` over
``decodes``, the engine's own count) and the held experts it touched
(``stats()["moe"]``: ``decode_touched`` and ``decode_pairs`` over ``decodes``,
counted on the device), between the readings at the slice's two ends; where
those two coincide (each waits for the engine's lock, and both can be
answered in one instant), between the readings at the window's two ends.
None where the program counts none of it (the parent of the PR that added
the family).  ``scope_ms`` is ``_decode_scope``'s with this occupancy beside
the milliseconds on the ``program_spans`` line.  (A package of one module:
``tests/test_harness.py`` lists the ``.py`` files this directory may hold.)"""

from _decode_scope import scope_ms as _scope_ms


def occupancy(run):
    c = run.get("counters") or {}
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}) for at in ends)
        pa, pb, ma, mb = a.get("kv_pool"), b.get("kv_pool"), a.get("moe"), b.get("moe")
        if not (pa and pb and ma and mb) or "decode_tokens" not in pb:
            continue
        n, m = pb["decodes"] - pa["decodes"], mb["decodes"] - ma["decodes"]
        if n > 0 and m > 0:
            return {"live_rows": (pb["decode_rows"] - pa["decode_rows"]) / n,
                    "live_tokens": (pb["decode_tokens"] - pa["decode_tokens"]) / n,
                    "touched": (mb["decode_touched"] - ma["decode_touched"]) / m,
                    "pairs": (mb["decode_pairs"] - ma["decode_pairs"]) / m,
                    "between": list(ends)}
    return None


def scope_ms(run, scopes, **beside):
    """Device milliseconds a decode in ``scopes`` (a name or several), or
    None where one of them is missing."""
    live = occupancy(run)
    if live is None:
        return None
    total = 0.0
    for scope in [scopes] if isinstance(scopes, str) else scopes:
        ms = _scope_ms(run, scope, **live, **beside)
        if not ms:
            return None
        total += ms
    return total
