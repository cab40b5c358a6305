"""``moe_hybrid_decode_dev_ms`` for a hybrid body WITHOUT a shared expert:
device milliseconds per decode execution in the leaf ops of the routed expert
layer's two scopes, ``moe_router`` and ``moe_experts`` (first chip), each on a
``program_spans`` line beside what the device counted of the slice's decodes
(``stats()["moe"]``: touched experts, pairs and the rows the expert layer
computed a decode).  The dense MLPs of the leading layers have a scope of
their own (``dense_mlp``) and are not the expert layer's.  None where the
program has no such scopes or counts, or has a ``moe_shared`` scope beside
them (``moe_hybrid_decode_dev_ms`` reads that one).

``counted`` and ``scopes_ms`` take the step's kind, so that the chunk's
readers (``moe_chunk_*``) are the same code over ``chunk_*`` counts and the
prefill programs."""

import bisect

import _inner_scope
from _common import trace_reduce
from _inner_scope import DECODE, PREFILL, scope_seconds

from benchmark import harness as H

SCOPES = ("moe_router", "moe_experts")
PROGRAMS = {"decode": DECODE, "chunk": PREFILL}


def counted(run, phase: str = "decode"):
    """Per ``phase`` step between two ``stats()`` readings (the slice's own
    pair, else the window's): held experts touched, pairs, rows the expert
    layer computed, and how many steps the means are over
    (``counted_steps``)."""
    c = run.get("counters") or {}
    steps = f"{phase}s"
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}).get("moe") for at in ends)
        if a and b and f"{phase}_touched" in b and f"{phase}_tile_rows" in b \
                and b[steps] > a[steps]:
            n = b[steps] - a[steps]
            per = lambda key: (b[f"{phase}_{key}"] - a[f"{phase}_{key}"]) / n  # noqa: E731
            return {"touched": per("touched"), "pairs": per("pairs"),
                    "tile_rows": per("tile_rows"), "between": list(ends), "counted_steps": n}
    return None


def counted_steps_ms(run, phase: str, scope: str, steps: int, **beside):
    """``_inner_scope.per_step_ms`` over the LAST ``steps`` executions of the
    phase's program that the trace holds, so that the time is of the steps the
    counts are of.  The slice's first ``stats()`` reading waits for the
    device's counters and returns some steps AFTER the trace began (84 quick
    decodes at 3 live rows in one run, before the 77 the readings bracketed:
    the share then read 150 against the window's counts and 72 for the
    attention against the slice's), and its second is made just before the
    trace is stopped: the counted steps are the trace's last ones, to a step
    or two.  ``traced_steps`` on the line is what the trace held in all."""
    spans = _inner_scope.load(run)
    if spans is None:
        return None
    trace, program = spans["trace"], PROGRAMS[phase]
    traced = sorted(m for m in trace["modules"] if program.search(m[2]))
    modules = traced[-steps:]
    if not modules:
        return None
    starts, segment, ops = [m[0] for m in modules], f"/{scope}/", {}
    for name, s, d in trace_reduce.leaf_ops(trace["ops"]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= modules[i][1]:
            continue
        if segment in "/" + trace["op_names"].get(name, "") + "/":
            short = trace_reduce.short_name(name)
            ops[short] = ops.get(short, 0.0) + d * 1e-9
    if not ops:
        return None
    ms = 1e3 * sum(ops.values()) / len(modules)
    H.emit("program_spans", scope=scope, program=program.pattern, ms_per_step=ms,
           executed=len(modules), traced_steps=len(traced), largest_op=max(ops, key=ops.get),
           **beside)
    return ms


#: (id of a run, phase) -> (the run, the two scopes' summed milliseconds): a share
#: is read right after its time, and a pass over a slice's ops takes seconds
_READ = {}


def scopes_ms(run, phase: str = "decode", **beside):
    """The two scopes' milliseconds a step, summed, over the steps ``counted``
    counted, or None where one of them or the device's counts are missing, or
    the program has a shared expert.  Read once a run and phase: a second call
    gives the first one's number and prints no line."""
    seen = _READ.get((id(run), phase))
    if seen is not None and seen[0] is run:
        return seen[1]
    live = counted(run, phase)
    if live is None or scope_seconds(run, PROGRAMS[phase], "moe_shared") is not None:
        return None
    total = 0.0
    for scope in SCOPES:
        ms = counted_steps_ms(run, phase, scope, live["counted_steps"], **live, **beside)
        if not ms:
            return None
        total += ms
    _READ[(id(run), phase)] = (run, total)
    return total


def read(run):
    return scopes_ms(run)
