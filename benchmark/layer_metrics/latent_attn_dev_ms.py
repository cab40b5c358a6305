"""Device milliseconds per decode execution in the leaf ops whose ``op_name``
lies in the ``latent_attention`` scope: every layer's absorbed attention of
the live rows over their paged latent rows (first chip), with the slice's
live rows, live tokens and touched experts beside it on a ``program_spans``
line.  None where the program has no such scope."""

from _latent_decode import scope_ms


def read(run):
    return scope_ms(run, "latent_attention")
