"""Device milliseconds per decode execution in the leaf ops whose ``op_name``
lies in the ``gqa_attention`` scope: every layer's grouped-query attention of
the live rows over their paged K/V (first chip), with the slice's live rows
and live tokens beside it on a ``program_spans`` line.  None where the
program has no such scope."""

from _inner_scope import DECODE, decode_occupancy, per_step_ms


def read(run):
    return per_step_ms(run, DECODE, "gqa_attention", **(decode_occupancy(run) or {}))
