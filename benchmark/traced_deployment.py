"""The program's ``LLMDeployment`` with ONE method replaced for a traced
run: ``stop_trace`` writes the profiler's ``.xplane.pb`` and nothing else.

``jax.profiler.stop_trace`` (``LLMDeployment.stop_trace``'s body) also
turns every event into a trace-viewer JSON (``*.trace.json.gz``), which no
reader here opens.  On a v5e that conversion is five sixths of stopping:
6.38 s against 1.06 s for 180,000 device ops on an idle process (my chip
run, PR 31), and in a serving run stopping costs 0.13 ms a device op, 0.9 s
an engine step of the four-chip cell, which is what ran the traced runs of
PRs 28 and 30 past the driver's 360 s (PERF.md, section 6).  ``start_trace``
stays the program's.  The session's ``stop()`` is jax's own first half of
``stop_trace``; where a jax has no such session, the program's method runs.
"""

import os
import socket
import time

from ray_tpu.serve.llm import LLMDeployment


class TracedLLMDeployment(LLMDeployment):
    def stop_trace(self) -> None:
        from jax._src import profiler

        state = getattr(profiler, "_profile_state", None)
        session = getattr(state, "profile_session", None)
        if not hasattr(session, "stop") or not hasattr(state, "reset"):
            return super().stop_trace()
        with state.lock:
            space = session.stop()  # the serialized XSpace
            run = os.path.join(str(state.log_dir), "plugins", "profile",
                               time.strftime("%Y_%m_%d_%H_%M_%S"))
            os.makedirs(run, exist_ok=True)
            with open(os.path.join(run, socket.gethostname() + ".xplane.pb"), "wb") as f:
                f.write(space)
            state.reset()
