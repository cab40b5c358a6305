"""The replica's first touch of the backend (``jax.devices()`` at the top of
``LLMDeployment.__init__``: plugin discovery, the chips opened), the
ledger's ``backend_init`` phase."""

from _startup_ledger import phase


def read(run):
    return phase(run, "backend_init")
