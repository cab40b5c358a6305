"""JAX's persistent compilation cache, placed from outside.

Every process that builds jitted steps for an accelerator calls
``ensure_compile_cache()`` before its first big compile
(``PagedModelRunner.__init__``, ``parallel.train_step.make_step_fn``) —
never at import time.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: do nothing in code, jax reads the
  variable itself.  Worker processes inherit the driver's environment,
  so a value set before ``ray_tpu.init()`` reaches serve replicas and
  train workers.
* unset, on an accelerator backend: one FIXED path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
  key, so it is never a tempfile, pid or timestamp path — a directory
  that moves never hits.
* unset, on the CPU backend: nothing.  CPU test runs must not fill a
  directory the chip tool copies with the tree.

jax's own thresholds stay as installed (only compiles of a second or
more are written), so the cache holds the model steps, not every helper.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_COUNTS = {"requests": 0, "hits": 0}
_LOCK = threading.Lock()
_listening = False


def compile_cache_dir(platform: str) -> Optional[str]:
    """The directory code must set for a process computing on
    ``platform``, or None when code sets nothing (module doc)."""
    if os.environ.get(ENV_VAR) or platform == "cpu":
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _COUNTS["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _COUNTS["hits"] += 1


def ensure_compile_cache() -> None:
    """Apply the rule in this process (idempotent) and start counting
    cache requests and hits for ``stats()``."""
    import jax

    global _listening
    with _LOCK:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    path = compile_cache_dir(jax.default_backend())
    if path is not None and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)


def stats() -> dict:
    """Where this process's cache lives (None: no persistent cache) and
    how many cacheable compiles it asked for / found there since
    ``ensure_compile_cache()``."""
    import jax

    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "requests": _COUNTS["requests"],
        "hits": _COUNTS["hits"],
    }
