"""A decode's device time in one named scope, read at a stated occupancy.

``scope_ms(run, scope)``: device milliseconds per decode execution in the
leaf ops whose ``op_name`` lies in ``scope`` (first chip), or None where the
slice holds no decode or the program has no such scope.  ``occupancy(run)``:
the live rows and the live tokens of context a decode of the slice stood at,
the engine's own count (``stats()["state_pool"]``: ``decode_rows`` and
``decode_tokens`` over ``decodes``) between the readings at the slice's two
ends; where those two readings coincide (each waits for the engine's lock,
and both can be answered in one instant), between the readings at the
window's two ends; None where the program counts no ``decode_tokens``.  Every
reading goes onto a ``program_spans`` line, the scope's milliseconds beside
the rows, the tokens and the pair of readings they came from."""

from _program_spans import load

from benchmark import harness as H


def occupancy(run):
    c = run.get("counters") or {}
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}).get("state_pool") for at in ends)
        if a and b and "decode_tokens" in b and b["decodes"] > a["decodes"]:
            n = b["decodes"] - a["decodes"]
            return {"live_rows": (b["decode_rows"] - a["decode_rows"]) / n,
                    "live_tokens": (b["decode_tokens"] - a["decode_tokens"]) / n,
                    "between": ends}
    return None


def scope_ms(run, scope: str, **beside):
    spans = load(run)
    if spans is None or not spans["decodes"]:
        return None
    ops = (spans.get("decode_by_scope") or {}).get(scope)
    if not ops:
        return None
    ms = 1e3 * sum(ops.values()) / spans["decodes"]
    H.emit("program_spans", scope=scope, ms_per_decode=ms, decodes=spans["decodes"],
           **(occupancy(run) or {}), **beside)
    return ms
