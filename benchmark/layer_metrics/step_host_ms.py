"""Host milliseconds of one engine step that are NOT a wait for the device:
admit, building the inputs, the two launches, the per-token emit loop and
the gauges, from ``stats()["step_phase_s"]`` (the engine's own account of
its step, ISSUE 23) over the steps of the window.  ``prefill_sample`` and
``decode_fetch`` block on the device and are left out."""

from _common import delta

HOST_PHASES = ("admit", "prefill_build", "prefill_launch", "decode_build",
               "decode_launch", "emit", "publish")


def read(run):
    steps = delta(run, "steps")
    parts = [delta(run, "step_phase_s", k) for k in HOST_PHASES]
    if not steps or any(p is None for p in parts):
        return None
    return 1e3 * sum(parts) / steps
