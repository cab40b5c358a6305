"""``moe_decode_dev_ms`` for a body whose sequences hold a state beside their
blocks (``cache_kind == "hybrid"``): device milliseconds per decode execution
in the leaf ops of the three expert-layer scopes, ``moe_router``,
``moe_experts`` and ``moe_shared`` (first chip), each on a ``program_spans``
line beside what the device counted of the slice's decodes
(``stats()["moe"]``: touched experts, pairs and the tile loop's rows a
decode).  A reader of its own because ``moe_decode_dev_ms`` hands
``_decode_scope.scope_ms`` the occupancy that function also reads from
``stats()["state_pool"]``, which such a body has (PERF.md section 7).  None
where the program has no such scopes or counts."""

from _inner_scope import DECODE, per_step_ms

SCOPES = ("moe_router", "moe_experts", "moe_shared")


def counted(run):
    """Per decode of the slice (of the window where the slice's two readings
    coincide): held experts touched, pairs, rows the tiles computed."""
    c = run.get("counters") or {}
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}).get("moe") for at in ends)
        if a and b and "decode_tile_rows" in b and b["decodes"] > a["decodes"]:
            n = b["decodes"] - a["decodes"]
            return {"touched": (b["decode_touched"] - a["decode_touched"]) / n,
                    "pairs": (b["decode_pairs"] - a["decode_pairs"]) / n,
                    "tile_rows": (b["decode_tile_rows"] - a["decode_tile_rows"]) / n,
                    "between": list(ends)}
    return None


def scopes_ms(run, **beside):
    """The three scopes' milliseconds a decode, summed, or None where one of
    them or the device's counts are missing."""
    live = counted(run)
    if live is None:
        return None
    total = 0.0
    for scope in SCOPES:
        ms = per_step_ms(run, DECODE, scope, **live, **beside)
        if not ms:
            return None
        total += ms
    return total


def read(run):
    return scopes_ms(run)
