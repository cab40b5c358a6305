"""What the PROGRAM's own instrumentation puts into a traced run, for the
readers that need more of the trace than ``trace_reduce`` keeps: host
spans named ``llm.*`` (``jax.profiler.TraceAnnotation``s of the engine's
step loop, on the profiler's clock) and the ``op_name`` of each device op
(the ``jax.named_scope`` path of the op, e.g. ``jit(_decode_impl)/.../
sample/...``).  Opens ``trace_reduce.find_xplane(run["trace_dir"])``
itself, once per run.  (A package of one module, not ``_program_spans.py``:
``tests/test_harness.py`` lists the ``.py`` files this directory may hold
beside the readers, and a PR that adds readers may not edit it.)

Where the ``op_name`` is (looked at by hand in a chip trace, PERF.md): the
op event's name is the HLO text WITHOUT metadata and the event's own stats
hold only ``device_offset_ps`` / ``device_duration_ps``; the op_name sits
in the event METADATA's stat ``tf_op``, which ``jax.profiler.ProfileData``
does not expose.  So events and times come from ``ProfileData`` and the
metadata from the ``.xplane.pb`` itself, read at the wire level (three
message types of tsl's ``xplane.proto``, whose field numbers are below).

A program without the instrumentation (the parent of the PR that added
it) leaves no ``llm.*`` span and no scoped op_name: ``load`` still
returns, the readers find nothing and return None.
"""

import bisect
import re

from _common import trace_reduce

from benchmark import harness as H

#: host spans of the engine's loop thread that say what it was doing; the
#: whole-step span ``llm.step`` contains them and explains nothing, and
#: ``llm.submit.*`` runs on callers' threads beside the loop
PHASE_SPAN = re.compile(r"llm\.(step\.|loop\.)")
#: the host span that ends when a decode's results are on the host
FETCH_SPAN = re.compile(r"llm\.step\.(decode|verify)_fetch$")
LAUNCH_SPAN = re.compile(r"llm\.step\.(decode|verify)_launch$")
DECODE_PROGRAM = re.compile(r"decode|verify")
#: the event metadata's stat that carries the HLO ``op_name``
OP_NAME_STAT = "tf_op"

_cache: dict = {}


# -- the xplane at the wire level --------------------------------------------


def _varint(buf, i):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def op_names(path: str, plane_name: str) -> dict:
    """event name (HLO text) -> ``op_name`` for the ops of one device plane.
    XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5 (maps:
    key=1, value=2); XEventMetadata.name=2 .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1 .str_value=5 .ref_value=7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == plane_name for f, v in parts):
            continue
        stat_names = {}
        for f, v in parts:
            if f == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for g, x in _fields(meta) if g == 2), "")
        out = {}
        for f, v in parts:
            if f != 4:
                continue
            _key, meta = _map_entry(v)
            name, op = None, None
            for g, x in _fields(meta):
                if g == 2:
                    name = bytes(x).decode()
                elif g == 5:
                    stat = dict(_fields(x))
                    if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                        continue
                    if 5 in stat:
                        op = bytes(stat[5]).decode()
                    elif 7 in stat:  # a reference to an interned string
                        op = stat_names.get(stat[7])
            if name and op:
                out[name] = op
        return out
    return {}


# -- one traced run ------------------------------------------------------------


def read_trace(path: str) -> dict:
    """First TPU plane's ops ``(raw name, start_ns, dur_ns)`` and programs
    ``(start_ns, end_ns, name)``, every host span named ``llm.*`` as
    ``(name, start_ns, end_ns)``, and the ops' op_names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)  # an iterator: walked twice below
    tpus = sorted(p.name for p in planes if re.fullmatch(r"/device:TPU:\d+", p.name))
    first = tpus[0] if tpus else None
    ops, modules, spans = [], [], []
    for plane in planes:
        for line in plane.lines:
            if plane.name == first and line.name == trace_reduce.OPS_LINE:
                ops = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            elif plane.name == first and line.name == trace_reduce.MODULES_LINE:
                modules = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     trace_reduce.program_name(e.name)) for e in line.events)
            elif not plane.name.startswith("/device:"):
                spans += [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                          for e in line.events if e.name.startswith("llm.")]
    return {"ops": ops, "modules": modules, "spans": sorted(spans, key=lambda s: s[1]),
            "op_names": op_names(path, first) if first and spans else {}}


def clock_offset_ns(trace: dict) -> tuple:
    """(offset, lower bound, upper bound) in ns: what to ADD to the device
    plane's times to put them on the host spans' clock.  The two are
    written by different clocks (on a v5e a program was seen to start 0.6
    ms BEFORE the span that launched it).  Two things order them: a decode
    program cannot start before its launch span does (lower bound: the
    largest launch start - program start) and its fetch span cannot end
    before the program does (upper bound: the smallest fetch end - program
    end).  The offset used is the middle, and half the distance between
    the bounds is how far a gap's label can be off; but never more than
    1.5 ms under the upper bound: in a short slice every decode may have
    queued behind a prefill chunk, and the lower bound then says nothing
    (tight bounds were seen 2.3-2.7 ms apart)."""
    decodes = [m for m in trace["modules"] if DECODE_PROGRAM.search(m[2])]
    launches = [sp for sp in trace["spans"] if LAUNCH_SPAN.search(sp[0])]
    lower, upper = [], []
    for name, start, end in trace["spans"]:
        if not FETCH_SPAN.search(name) or not decodes:
            continue
        prog = min(decodes, key=lambda m: abs(end - m[1]))
        if abs(end - prog[1]) > 20e6:  # steps are ~100 ms apart
            continue
        upper.append(end - prog[1])
        before = [sp for sp in launches if sp[1] < start]
        if before and start - before[-1][1] < 20e6:
            lower.append(before[-1][1] - prog[0])
    if not upper:
        return 0.0, None, None
    hi = min(upper)
    lo = max(lower) if lower and max(lower) <= hi else None
    return (hi if lo is None else max(0.5 * (lo + hi), hi - 1.5e6)), lo, hi


def idle_by_span(trace: dict, offset_ns: float) -> tuple:
    """(idle seconds of the chip in the slice, seconds of them under a
    phase span, {span name: seconds}).  A gap is a maximal interval with no
    op on the device; each of its nanoseconds goes to the phase span that
    covers it (phase spans of one thread do not overlap)."""
    merged = sorted((s + offset_ns, s + d + offset_ns) for _n, s, d in trace["ops"])
    gaps, cur_end = [], merged[0][1] if merged else 0.0
    for s, e in merged[1:]:
        if s > cur_end:
            gaps.append((cur_end, s))
        cur_end = max(cur_end, e)
    phases = [sp for sp in trace["spans"] if PHASE_SPAN.match(sp[0])]
    by_span, j = {}, 0
    for g0, g1 in gaps:
        while j < len(phases) and phases[j][2] <= g0:
            j += 1
        k = j
        while k < len(phases) and phases[k][1] < g1:
            cover = min(g1, phases[k][2]) - max(g0, phases[k][1])
            if cover > 0:
                by_span[phases[k][0]] = by_span.get(phases[k][0], 0.0) + cover * 1e-9
            k += 1
    idle = sum(g1 - g0 for g0, g1 in gaps) * 1e-9
    return idle, sum(by_span.values()), by_span


#: segments of an op_name that are structure, not a ``jax.named_scope``;
#: autodiff and vmap WRAP a segment (``transpose(jvp(ce))``)
_STRUCTURE = re.compile(r"(jit|pjit)\(.*\)$|"
                        r"(while|body|cond|scan|closed_call|checkpoint|core_call|shard_map)$")
_WRAPPED = re.compile(r"(jvp|transpose|vmap|remat|custom_jvp|custom_vjp)\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The outermost named scope of an op: the first segment of its name
    stack that is neither structure (``jit(...)``, ``while``, ``body``) nor
    the op's own primitive (the last segment); ``-`` where there is none
    (the scan's own copies of its operands, for one)."""
    parts = [p for p in op_name.rstrip(":").split("/") if p]
    for part in parts[:-1]:
        while (m := _WRAPPED.match(part)):
            part = m.group(2)
        if part and not _STRUCTURE.match(part):
            return part
    return "-"


def seconds_by_scope(trace: dict, program: re.Pattern) -> dict:
    """{scope: {op: device seconds}} of the leaf ops that ran while a
    program matching ``program`` did, the largest scope first."""
    starts = [m[0] for m in trace["modules"]]
    out: dict = {}
    for name, s, d in trace_reduce.leaf_ops(trace["ops"]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= trace["modules"][i][1] or not program.search(trace["modules"][i][2]):
            continue
        ops = out.setdefault(scope_of(trace["op_names"].get(name, "")), {})
        short = trace_reduce.short_name(name)
        ops[short] = ops.get(short, 0.0) + d * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -sum(kv[1].values())))


def _summary(by_scope: dict) -> dict:
    """For the progress line: {scope: [seconds, its largest op]}."""
    return {k: [sum(v.values()), max(v, key=v.get)] for k, v in by_scope.items()}


def load(run: dict):
    """The traced slice of this run as the readers need it, or None when
    there is no trace or the program wrote no ``llm.*`` span into it.  The
    file is parsed ONCE a run: the summary keeps the parsed ``trace`` and
    the decode programs' seconds by scope (``decode_by_scope``) for the
    readers that need more than the summary.
    Emits one ``program_spans`` progress line: the idle seconds by span
    (PERF.md's gap table), the sampler's ops and each step program's device
    seconds by named scope."""
    trace_dir = run.get("trace_dir")
    if not trace_dir:
        return None
    if trace_dir not in _cache:
        trace = read_trace(trace_reduce.find_xplane(trace_dir))
        out = None
        if trace["spans"] and trace["ops"]:
            offset, lo, hi = clock_offset_ns(trace)
            idle, covered, by_span = idle_by_span(trace, offset)
            decode = seconds_by_scope(trace, DECODE_PROGRAM)
            sample = decode.get("sample", {})
            decodes = sum(1 for m in trace["modules"] if DECODE_PROGRAM.search(m[2]))
            out = {"idle_s": idle, "idle_covered_s": covered,
                   "sample_s": sum(sample.values()), "decodes": decodes,
                   "trace": trace, "decode_by_scope": decode}
            H.emit("program_spans", device_clock_offset_ms=offset * 1e-6,
                   offset_bounds_ms=[None if b is None else b * 1e-6 for b in (lo, hi)],
                   spans=len(trace["spans"]), ops_with_op_name=len(trace["op_names"]),
                   idle_s=idle, idle_covered_s=covered,
                   idle_by_span=dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
                   decodes=decodes, decode_by_scope=_summary(decode),
                   sample_ops=dict(sorted(sample.items(), key=lambda kv: -kv[1])[:4]),
                   prefills=sum(1 for m in trace["modules"] if "prefill" in m[2]),
                   prefill_by_scope=_summary(seconds_by_scope(trace, re.compile("prefill"))))
        _cache[trace_dir] = out
    return _cache[trace_dir]
