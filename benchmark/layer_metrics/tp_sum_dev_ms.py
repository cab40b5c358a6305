"""Device milliseconds per decode execution in the leaf ops whose
``op_name`` lies in the ``tp_sum`` scope (``llm.multichip._tp_sum``): the
all-gather AND the float32 adds that follow it, first chip.  Beside the
number, one more ``program_spans`` progress line splits those ms by the
scope nested under ``tp_sum`` (``gather``: data movement; ``add``: the
summation; ``tp_sum`` itself where a program has no nested scope)."""

import re

from _common import trace_reduce
from _program_spans import DECODE_PROGRAM, load, read_trace, seconds_by_scope

from benchmark import harness as H

NESTED = re.compile(r"/tp_sum/(\w+)/")


def read(run):
    spans = load(run)
    if spans is None or not spans["decodes"]:
        return None
    # ``load`` keeps its parse (PR 31); a summary without one (tier-1's
    # ``tests/test_benchmark_tp_sum_reader.py`` hands such a one in) is
    # read from the file as before
    trace = spans.get("trace") or read_trace(trace_reduce.find_xplane(run["trace_dir"]))
    ops = (spans.get("decode_by_scope") or seconds_by_scope(trace, DECODE_PROGRAM)).get("tp_sum")
    if not ops:
        return None
    half = {trace_reduce.short_name(raw): m.group(1)
            for raw, op in trace["op_names"].items() if (m := NESTED.search(op))}
    per_decode, by_half = 1e3 / spans["decodes"], {}
    for op, seconds in ops.items():
        key = half.get(op, "tp_sum")
        by_half[key] = by_half.get(key, 0.0) + seconds * per_decode
    H.emit("program_spans", scope="tp_sum", decodes=spans["decodes"],
           ms_per_decode_by_scope=by_half,
           ms_per_decode_by_op={op: s * per_decode for op, s in ops.items()})
    return sum(ops.values()) * per_decode
