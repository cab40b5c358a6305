"""The SSD decode kernel's share of its roofline.  It is memory-bound: the
least time is the bytes of state a decode must move (the family's
``ssd_decode_state_bytes``: each live row's state of every layer and head,
``P x N`` float32, read once and written once, unpadded) over the chip's HBM
bandwidth; the time taken is the kernel's summed device time in the slice
(the Pallas call under the ``ssd_update`` scope, inside ``ssm``) over the
decode programs executed.  The live rows are the engine's own count
(``_decode_scope.occupancy``) and stand on the ``program_spans`` line beside
the share."""

from _common import family_piece
from _inner_scope import DECODE, decode_occupancy, per_step_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = decode_occupancy(run)
    if live is None:
        return None
    need = family_piece(run["config"], "ssd_decode_state_bytes")(live["live_rows"], run["model"])
    ms = per_step_ms(run, DECODE, "ssd_update", kernel_only=True, state_bytes=need, **live)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
