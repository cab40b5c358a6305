"""Device milliseconds per prefill-chunk execution in the leaf ops whose
``op_name`` lies in the ``chunk_attention`` scope: every layer's attention of
the chunk's queries over the sequence's paged K/V, walked in runs of blocks
(first chip), with the slice's chunks, their valid tokens and the context
they reached (what the walk's length follows) beside it on a
``program_spans`` line.  None where the program has no such scope or the
slice holds no chunk."""

from _inner_scope import PREFILL, chunk_occupancy, per_step_ms


def read(run):
    return per_step_ms(run, PREFILL, "chunk_attention", **(chunk_occupancy(run) or {}))
