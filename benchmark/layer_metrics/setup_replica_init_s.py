"""The program's whole share of ``setup_s`` in a serving cell: from the
replica worker's first line (or the arrival of its fork request) to the
instant ``LLMDeployment.__init__`` returned, both stamped by the worker on
the host's wall clock (``device_report()["startup"]``)."""

from _startup_ledger import ledger


def read(run):
    led = ledger(run)
    if led is None or led.get("t_ready") is None or led.get("t_process_start") is None:
        return None
    return led["t_ready"] - led["t_process_start"]
