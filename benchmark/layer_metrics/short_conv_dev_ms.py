"""Device milliseconds per decode execution in the leaf ops whose ``op_name``
lies in the ``short_conv`` scope: every conv layer's gated short convolution
(its norm, ``W_in``, both gates, the taps over the slot's tails, ``W_out``) for
the live rows (first chip), with the slice's live rows beside it on a
``program_spans`` line.  None where the program has no such scope."""

from _inner_scope import DECODE, decode_occupancy, per_step_ms


def read(run):
    return per_step_ms(run, DECODE, "short_conv", **(decode_occupancy(run) or {}))
