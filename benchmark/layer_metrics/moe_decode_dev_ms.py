"""Device milliseconds per decode execution in the leaf ops of the three
expert-layer scopes, ``moe_router``, ``moe_experts`` and ``moe_shared``
(first chip): the float32 router over all experts, the tiles of (row, held
expert) pairs and the shared expert, in every expert layer, with the slice's
live rows and touched experts beside each on a ``program_spans`` line.  None
where the program has no such scopes."""

from _latent_decode import scope_ms

SCOPES = ("moe_router", "moe_experts", "moe_shared")


def read(run):
    return scope_ms(run, SCOPES)
