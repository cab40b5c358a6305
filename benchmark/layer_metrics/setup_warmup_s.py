"""``LLMEngine.warmup()`` whole: every step program traced, lowered,
compiled or loaded from the persistent cache, and run once (the ledger's
``warmup`` phase; ``first_call`` on the ``startup_ledger`` line splits it
by program)."""

from _startup_ledger import phase


def read(run):
    return phase(run, "warmup")
