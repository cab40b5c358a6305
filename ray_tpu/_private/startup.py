"""Start-up ledger: where the seconds between a process's first line and
its first useful step went.  ONE record a process, kept by the process
that holds the chip: a serve replica's worker (``serve.llm.LLMDeployment``)
or a train worker (``train._session``).  ``worker_main`` stamps the
process's start, the actor's ``__init__`` / the train loop stamps its own
entry, and the phases in between time themselves with one ``perf_counter``
pair that feeds BOTH a span ``startup.<phase>`` on the profiler's clock
(``util.tracing.annotate``: under ``jax.profiler.start_trace`` an idle gap
of the chip during set-up gets the phase's name) and the cumulative
``phases_s`` here, as ``llm.engine`` does for ``step_phase_s``.

Read as ``device_report()["startup"]`` (``util.device_prof`` and the
engine's) and ``LLMDeployment.stats()["startup"]``; OBSERVABILITY.md,
"Start-up ledger".  Standard library only: a worker that never computes
stamps its start and still imports no jax.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

#: the phases of ``__init__`` a replica times by name, in their order;
#: ``other`` is what they leave of ``t_ready - t_init_begin``
INIT_PHASES = ("backend_init", "weights", "engine_init", "warmup")

_LOCK = threading.Lock()
#: instants on the host's WALL clock (``time.time()``), so that the
#: harness's marks, the controller's events and the worker's lie on one axis
_T: dict = {"t_process_start": None, "t_init_begin": None, "t_ready": None}
_PHASES: dict = {}
#: ``perf_counter`` at ``init_begin`` / ``ready``: the whole that the
#: phases (the same clock) are parts of, so ``other`` closes the sum exactly
_PC: dict = {"init_begin": None, "ready": None}
_import_s: Optional[float] = None
_weights_done_s: Optional[float] = None


def process_started(t: float) -> None:
    """``t_process_start``: the first stamp of a process wins (a worker
    forked from the template was stamped with the arrival of its fork
    request before ``worker_main.main`` stamps its own first line)."""
    with _LOCK:
        if _T["t_process_start"] is None:
            _T["t_process_start"] = t


def imported(seconds: float) -> None:
    """The ``import`` part of ``worker_boot``: what the module of the
    actor's class spent in its own imports (jax among them)."""
    global _import_s
    with _LOCK:
        if _import_s is None:
            _import_s = seconds


def init_begin() -> float:
    """The actor's ``__init__`` / the train loop is entered: a new account
    of the init phases begins (one process builds one replica; a test that
    builds several reads the last)."""
    global _weights_done_s
    now = time.time()
    with _LOCK:
        _T["t_init_begin"], _T["t_ready"] = now, None
        _PC["init_begin"], _PC["ready"] = time.perf_counter(), None
        _PHASES.clear()
        _weights_done_s = None
    return now


@contextlib.contextmanager
def phase(name: str):
    """Time one phase of set-up: a span ``startup.<name>`` where a profiler
    can exist, and the same seconds into ``phases_s[name]``."""
    from ray_tpu.util import tracing

    t0 = time.perf_counter()
    try:
        with tracing.annotate("startup." + name):
            yield
    finally:
        dt = time.perf_counter() - t0
        with _LOCK:
            _PHASES[name] = _PHASES.get(name, 0.0) + dt


def stamp_when_ready(leaves: list) -> None:
    """``weights_device_done_s``: seconds from ``t_init_begin`` until the
    device has finished ``leaves`` (the weights, dispatched
    asynchronously), stamped by a short-lived thread that waits for them
    OFF the set-up path: the path itself never waits, so the weights'
    generation runs under the engine's construction and the warm-up's
    tracing as before."""
    begun = _PC["init_begin"]

    def wait():
        global _weights_done_s
        import jax

        jax.block_until_ready(leaves)
        with _LOCK:
            if _PC["init_begin"] == begun:  # still this replica's account
                _weights_done_s = time.perf_counter() - begun

    threading.Thread(target=wait, name="startup-weights-ready", daemon=True).start()


def ready() -> float:
    """``__init__`` returned: the replica answers from here on."""
    now = time.time()
    with _LOCK:
        _T["t_ready"], _PC["ready"] = now, time.perf_counter()
    return now


def report() -> dict:
    """The ledger as it stands.  ``phases_s`` holds ``worker_boot``
    (``t_init_begin - t_process_start``; with the class's module timed,
    also its parts ``import`` and ``spawn``, the rest) once the actor or
    the loop is entered, the init phases that ran, and, once ``t_ready``
    is stamped, ``other``: what the named init phases leave of
    ``t_ready - t_init_begin``."""
    with _LOCK:
        rep = dict(_T)
        phases = dict(_PHASES)
        if rep["t_process_start"] is not None and rep["t_init_begin"] is not None:
            boot = rep["t_init_begin"] - rep["t_process_start"]
            phases["worker_boot"] = boot
            if _import_s is not None:
                phases["import"], phases["spawn"] = _import_s, boot - _import_s
        if _PC["ready"] is not None:
            whole = _PC["ready"] - _PC["init_begin"]
            phases["other"] = whole - sum(phases.get(p, 0.0) for p in INIT_PHASES)
        rep["phases_s"] = phases
        rep["weights_device_done_s"] = _weights_done_s
    return rep
