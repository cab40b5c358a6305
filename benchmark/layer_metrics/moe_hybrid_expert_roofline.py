"""``moe_expert_roofline`` for a body whose sequences hold a state beside
their blocks (``moe_hybrid_decode_dev_ms`` says why it has readers of its
own): the expert layers' share of their roofline in decode.  At 16 rows they
are weight reads: the least time is the bytes the mathematics reads (the
family's ``moe_decode_bytes``: every layer's router and shared MLP, and an
expert for every held expert that at least one row chose, the device's own
count a decode) over the chip's HBM bandwidth; the time taken is the device
time of every leaf op under the three ``moe_*`` scopes in the slice over the
decode programs executed."""

from _common import family_piece
from moe_hybrid_decode_dev_ms import counted, scopes_ms


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = counted(run)
    if live is None:
        return None
    need = family_piece(run["config"], "moe_decode_bytes")(live["touched"], run["model"])
    ms = scopes_ms(run, moe_bytes=need)
    if not ms:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms * 1e-3)
