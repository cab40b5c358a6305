"""The grouped-query attention's share of its roofline in decode.  It is
memory-bound: the least time is the bytes the mathematics reads (the family's
``gqa_decode_kv_bytes``: every live token's K and V of every layer, once,
unpadded: the query heads that share a key-value head read it together) over
the chip's HBM bandwidth; the time taken is the device time of every leaf op
under the ``gqa_attention`` scope in the slice over the decode programs
executed.  The live tokens are the engine's own count and stand on the
``program_spans`` line beside the share."""

from _common import family_piece
from _inner_scope import DECODE, decode_occupancy, per_step_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = decode_occupancy(run)
    if live is None:
        return None
    need = family_piece(run["config"], "gqa_decode_kv_bytes")(live["live_tokens"], run["model"])
    ms = per_step_ms(run, DECODE, "gqa_attention", kv_bytes=need, **live)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
