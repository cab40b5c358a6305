"""The shared-K/V attention's share of its roofline in decode.  It is
memory-bound: the least time is the bytes the mathematics reads (the
family's ``shared_kv_decode_bytes``: the full-attention layer and each
cross layer read every live token's K and V of the one shared layer, counted
unpadded) over the chip's HBM bandwidth; the time taken is the device time
of every leaf op under the ``shared_kv_attention`` scope in the slice over
the decode programs executed (``_decode_scope``, which also says where the
live tokens come from and prints them beside the share)."""

from _common import family_piece
from _decode_scope import occupancy, scope_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = occupancy(run)
    if live is None:
        return None
    need = family_piece(run["config"], "shared_kv_decode_bytes")(
        live["live_tokens"], run["model"])
    ms = scope_ms(run, "shared_kv_attention", kv_bytes=need)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
