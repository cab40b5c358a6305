"""The routed expert layers' share of their roofline in a prefill chunk
(``moe_chunk_dev_ms``).  A chunk of 512 rows x 4 gives each of 64 experts 32
pairs: the least time is the LARGER of the bytes the mathematics reads (the
family's ``moe_chunk_bytes``: every expert layer's router and each touched
expert's weights once, the device's own count a chunk) over the chip's HBM
bandwidth, and the pairs' products (the family's ``moe_pair_flops`` a pair)
over its bfloat16 peak; the time taken is the device time of every leaf op
under ``moe_router`` and ``moe_experts`` in the slice over the prefill
programs executed."""

from _common import family_piece
from moe_routed_decode_dev_ms import SCOPES, counted, scopes_ms

from benchmark import harness as H


def read(run):
    if not run.get("peaks"):
        return None  # a rehearsal has no chip to compare with
    live = counted(run, "chunk")
    if live is None:
        return None
    need = family_piece(run["config"], "moe_chunk_bytes")(live["touched"], run["model"])
    flops = live["pairs"] * family_piece(run["config"], "moe_pair_flops")(run["model"])
    least = max(need / run["peaks"]["hbm_bytes_per_s"], flops / run["peaks"]["flops_bf16"])
    ms = scopes_ms(run, "chunk")
    if not ms:
        return None
    H.emit("program_spans", scope="+".join(SCOPES), program="prefill", ms_per_step=ms,
           moe_bytes=need, pair_flops=flops, **live)
    return 100.0 * least / (ms * 1e-3)
