"""The retention decode kernel's share of its roofline.  It is
memory-bound: the least time is the bytes of recurrent state a decode must
move (the family's ``retention_decode_state_bytes``: each live row's state
of every layer and key-value head, read once and written once) over the
chip's HBM bandwidth; the time taken is the kernel's summed device time in
the slice (the Pallas call under the ``retention`` scope) over the decode
programs executed.  The live rows are the engine's own count of the rows it
sent each decode (``stats()["state_pool"]``: ``decode_rows`` over
``decodes``) between the readings at the slice's two ends; where those two
readings coincide (each waits for the engine's lock, for seconds under a
backlog, and both can be answered in one instant), between the readings at
the window's two ends.  They stand on a ``program_spans`` line beside the
share, with the pair of readings they came from."""

from _common import family_piece
from _program_spans import load

from benchmark import harness as H


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    spans, c = load(run), run.get("counters") or {}
    if spans is None or not spans["decodes"]:
        return None
    ops = (spans.get("decode_by_scope") or {}).get("retention") or {}
    kernel_s = sum(s for op, s in ops.items() if "tpu_custom_call" in op)
    live = between = None
    for ends in (("trace_start", "trace_stop"), ("open", "close")):
        a, b = (c.get(at, {}).get("state_pool") for at in ends)
        if a and b and b["decodes"] > a["decodes"]:
            live = (b["decode_rows"] - a["decode_rows"]) / (b["decodes"] - a["decodes"])
            between = ends
            break
    if not kernel_s or live is None:
        return None
    need = family_piece(run["config"], "retention_decode_state_bytes")(live, run["model"])
    kernel_ms = 1e3 * kernel_s / spans["decodes"]
    H.emit("program_spans", scope="retention", live_rows=live, between=between, state_bytes=need,
           kernel_ms_per_decode=kernel_ms, decodes=spans["decodes"])
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (kernel_ms * 1e-3)
