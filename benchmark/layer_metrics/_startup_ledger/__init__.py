"""What the six ``setup_*`` readers share: the start-up ledger the process
on the chip keeps of itself (``ray_tpu/_private/startup.py``) and its
compile listener's seconds (``ray_tpu/_private/compile_cache.py``), as the
run's ``device_report`` carries them (``serving.py``'s ``after``, the train
loop's ``rep``).  A program without the ledger (a parent commit of PR 56)
gives every reader None: the metric is left out of the line.

Emits one ``startup_ledger`` progress line a run: the instants, every
phase, the compile split with ``hits`` / ``requests`` beside it, the
functions that cost most and each step's first call (PERF.md, section 5,
"Where set-up goes")."""

import _common  # noqa: F401  (puts the checkout's root on the path)

from benchmark import harness as H  # noqa: E402


def ledger(run):
    """The run's ``startup`` record, or None where the program keeps none."""
    rep = run.get("device_report") or {}
    led = rep.get("startup")
    if not isinstance(led, dict) or "phases_s" not in led:
        return None
    if not run.get("_startup_ledger_emitted"):  # six readers, one line
        run["_startup_ledger_emitted"] = True
        H.emit("startup_ledger", **led, compile_cache=rep.get("compile_cache"),
               first_call=rep.get("first_call"), setup_s=run.get("setup_s"))
    return led


def phase(run, name):
    led = ledger(run)
    return None if led is None else led["phases_s"].get(name)
