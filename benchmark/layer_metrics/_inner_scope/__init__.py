"""A step program's device time in a named scope that lies INSIDE another,
and what the steps of the slice stood at.

``_program_spans.scope_of`` gives an op its OUTERMOST scope (``ssm`` for the
whole Mamba-2 mixer, so that ``ssm_decode_dev_ms`` reads it); the SSD update
and the chunk form are scopes inside it.  ``scope_seconds(run, program,
scope)``: (device seconds of the leaf ops whose ``op_name`` has ``scope`` as a
path segment and that ran while a program matching ``program`` did, by op;
how many such programs the slice executed), first chip; None where there is
no trace, no such program in the slice or no such scope in the program (the
parent of the PR that added it).  ``per_step_ms`` is that as milliseconds a
program execution, printed on a ``program_spans`` line beside what the steps
stood at.

``decode_occupancy`` is ``_decode_scope.occupancy`` (live rows and live
tokens of context a decode); ``chunk_occupancy`` the same for prefill chunks
from ``stats()["state_pool"]``: ``chunks``, ``chunk_tokens`` (valid tokens)
and ``chunk_context_tokens`` (the positions a chunk's attention reached), a
chunk; None where the program counts none of it.  (A package of one module:
``tests/test_harness.py`` lists the ``.py`` files this directory may hold.)"""

import bisect
import re

from _common import trace_reduce
from _decode_scope import occupancy as decode_occupancy  # noqa: F401
from _program_spans import load

from benchmark import harness as H

DECODE = re.compile(r"decode|verify")
PREFILL = re.compile(r"prefill")


def scope_seconds(run, program: re.Pattern, scope: str):
    spans = load(run)
    if spans is None:
        return None
    trace = spans["trace"]
    modules = trace["modules"]
    executed = sum(1 for m in modules if program.search(m[2]))
    if not executed:
        return None
    starts, segment, ops = [m[0] for m in modules], f"/{scope}/", {}
    for name, s, d in trace_reduce.leaf_ops(trace["ops"]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= modules[i][1] or not program.search(modules[i][2]):
            continue
        if segment in "/" + trace["op_names"].get(name, "") + "/":
            short = trace_reduce.short_name(name)
            ops[short] = ops.get(short, 0.0) + d * 1e-9
    return (ops, executed) if ops else None


def chunk_occupancy(run):
    c = run.get("counters") or {}
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}).get("state_pool") for at in ends)
        if a and b and "chunks" in b and b["chunks"] > a["chunks"]:
            n = b["chunks"] - a["chunks"]
            return {"chunks": n,
                    "chunk_tokens": (b["chunk_tokens"] - a["chunk_tokens"]) / n,
                    "chunk_context_tokens":
                        (b["chunk_context_tokens"] - a["chunk_context_tokens"]) / n,
                    "between": list(ends)}
    return None


def per_step_ms(run, program: re.Pattern, scope: str, kernel_only: bool = False, **beside):
    """Milliseconds a program execution in ``scope`` (``kernel_only``: its
    Pallas kernels alone), or None."""
    found = scope_seconds(run, program, scope)
    if found is None:
        return None
    ops, executed = found
    seconds = sum(s for op, s in ops.items() if not kernel_only or "tpu_custom_call" in op)
    if not seconds:
        return None
    ms = 1e3 * seconds / executed
    H.emit("program_spans", scope=scope, program=program.pattern, ms_per_step=ms,
           executed=executed, kernel_only=kernel_only,
           largest_op=max(ops, key=ops.get), **beside)
    return ms
