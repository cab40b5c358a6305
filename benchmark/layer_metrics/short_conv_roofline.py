"""The gated short convolution's share of its roofline in decode.  At 16 rows
it is weight reads: the least time is the bytes the mathematics moves (the
family's ``short_conv_decode_bytes``: every conv layer's ``W_in``, taps and
``W_out`` once, each live row's tails read and written, unpadded) over the
chip's HBM bandwidth; the time taken is the device time of every leaf op under
the ``short_conv`` scope in the slice over the decode programs executed.  The
live rows are the engine's own count and stand on the ``program_spans`` line
beside the share."""

from _common import family_piece
from _inner_scope import DECODE, decode_occupancy, per_step_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = decode_occupancy(run)
    if live is None:
        return None
    need = family_piece(run["config"], "short_conv_decode_bytes")(live["live_rows"], run["model"])
    ms = per_step_ms(run, DECODE, "short_conv", conv_bytes=need, **live)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
