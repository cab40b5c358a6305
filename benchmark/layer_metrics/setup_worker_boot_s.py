"""Process start to the first line of the code that computes: in a serving
cell the replica worker's start to ``LLMDeployment.__init__`` entered
(spawn, registration, the actor's arguments, ``import jax``); in the train
cell the worker's start to ``train_loop_per_worker`` entered.  The
ledger's ``worker_boot``."""

from _startup_ledger import phase


def read(run):
    return phase(run, "worker_boot")
