"""Device milliseconds per decode execution in the leaf ops whose ``op_name``
lies in the ``shared_kv_attention`` scope: the differential attention of the
full-attention layer and of every cross layer over the ONE layer of paged K/V
(first chip), with the slice's live rows and tokens beside it on a
``program_spans`` line.  None where the program has no such scope."""

from _decode_scope import scope_ms


def read(run):
    return scope_ms(run, "shared_kv_attention")
