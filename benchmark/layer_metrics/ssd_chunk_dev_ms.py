"""Device milliseconds per prefill-chunk execution in the leaf ops whose
``op_name`` lies in the ``ssd_chunk`` scope (inside ``ssm``): every layer's
Mamba-2 chunk form, entered with the slot's state and leaving it (first
chip), with the slice's chunks, their valid tokens and the context they
reached beside it on a ``program_spans`` line.  None where the program has no
such scope or the slice holds no chunk."""

from _inner_scope import PREFILL, chunk_occupancy, per_step_ms


def read(run):
    return per_step_ms(run, PREFILL, "ssd_chunk", **(chunk_occupancy(run) or {}))
