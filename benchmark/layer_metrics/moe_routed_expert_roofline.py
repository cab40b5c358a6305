"""The routed expert layers' share of their roofline in decode, for a hybrid
body without a shared expert (``moe_routed_decode_dev_ms``).  At 16 rows they
are weight reads: the least time is the bytes the mathematics reads (the
family's ``moe_decode_bytes``: every expert layer's router, and an expert for
every held expert that at least one row chose, the device's own count a
decode) over the chip's HBM bandwidth; the time taken is the device time of
every leaf op under ``moe_router`` and ``moe_experts`` in the slice over the
decode programs executed."""

from _common import family_piece
from moe_routed_decode_dev_ms import SCOPES, counted, scopes_ms

from benchmark import harness as H


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = counted(run)
    if live is None:
        return None
    need = family_piece(run["config"], "moe_decode_bytes")(live["touched"], run["model"])
    ms = scopes_ms(run)
    if not ms:
        return None
    H.emit("program_spans", scope="+".join(SCOPES), program="decode", ms_per_step=ms,
           moe_bytes=need, **live)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
