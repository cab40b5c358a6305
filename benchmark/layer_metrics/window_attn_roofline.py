"""The window layers' paged attention's share of its roofline in decode.  It is
memory-bound: the least time is the bytes the mathematics reads (the family's
``window_decode_kv_bytes``: the K and V of the tokens a live row's query still
SEES in every window layer, ``min(context, window)`` of them a row, once,
unpadded) over the chip's HBM bandwidth; the time taken is the device time of
every leaf op under the ``window_attention`` scope in the slice over the decode
programs executed.  The tokens seen are the engine's own count
(``stats()["state_pool"]``: ``decode_window_tokens`` over ``decodes``, between
the readings the live tokens are taken between) and stand on the
``program_spans`` line beside the share.  None where the program counts no
such tokens (a program from before PR 69, a family with no paged window
layer) or has no such scope."""

from _common import family_piece
from _decode_scope import occupancy, scope_ms


def window_tokens(run):
    """Tokens the live rows of a decode saw in a window layer, a decode, over
    the pair of readings ``occupancy`` used; None where the program counts
    none."""
    live = occupancy(run)
    if live is None:
        return None
    a, b = (run["counters"][at]["state_pool"] for at in live["between"])
    if "decode_window_tokens" not in b:
        return None
    return (b["decode_window_tokens"] - a["decode_window_tokens"]) / (b["decodes"] - a["decodes"])


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    tokens = window_tokens(run)
    if tokens is None:
        return None
    need = family_piece(run["config"], "window_decode_kv_bytes")(tokens, run["model"])
    ms = scope_ms(run, "window_attention", window_tokens=tokens, kv_bytes=need)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
