"""Host seconds until the weights' ONE jitted program was traced, lowered,
compiled or loaded, and dispatched (``_build_model``): the ledger's
``weights`` phase.  The path does not wait for the device there; when the
leaves were ready is ``weights_device_done_s`` on the ``startup_ledger``
line."""

from _startup_ledger import phase


def read(run):
    return phase(run, "weights")
