"""The latent attention's share of its roofline in decode.  It sits at the
ridge: the least time is the LARGER of the bytes the mathematics reads (the
family's ``latent_decode_bytes``: every live token's row in every layer,
unpadded, a shared block counted once for every row that reads it) over the
chip's HBM bandwidth and the operations it needs (``latent_decode_flops``:
every head's score against the row and its sum over the row's latent part)
over the chip's bf16 peak; the time taken is the device time of every leaf op
under the ``latent_attention`` scope in the slice over the decode programs
executed (``_latent_decode``, which also says where the live tokens come
from and prints them, and which bound was the larger, beside the share)."""

from _common import family_piece
from _latent_decode import occupancy, scope_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = occupancy(run)
    if live is None:
        return None
    by_bytes = family_piece(run["config"], "latent_decode_bytes")(
        live["live_tokens"], run["model"]) / run["peaks"]["hbm_bytes_per_s"]
    by_flops = family_piece(run["config"], "latent_decode_flops")(
        live["live_tokens"], run["model"]) / run["peaks"]["flops_bf16"]
    ms = scope_ms(run, "latent_attention", least_ms_by_bytes=1e3 * by_bytes,
                  least_ms_by_flops=1e3 * by_flops)
    if not ms:
        return None
    return 100.0 * max(by_bytes, by_flops) / (ms * 1e-3)
