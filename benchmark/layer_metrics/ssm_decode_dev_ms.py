"""Device milliseconds per decode execution in the leaf ops whose ``op_name``
lies in the ``ssm`` scope: the causal convolution, the projections to the step
size and to B and C, the scan's update of every live row's state in every
state-space layer, and the gate (first chip), with the slice's live rows and
tokens beside it on a ``program_spans`` line.  None where the program has no
such scope."""

from _decode_scope import scope_ms


def read(run):
    return scope_ms(run, "ssm")
