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

The same call starts this process's account of its compiles, from
``jax.monitoring``: how many asked the cache and how many it answered, and
the seconds jax itself times for each program: tracing, lowering, and the
backend's compile OR the cache's load in its place (``stats()``;
OBSERVABILITY.md, "Start-up ledger").  Both listeners fire once a compile,
never on a call that finds its program in the jit cache.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_COUNTS = {"requests": 0, "hits": 0, "programs": 0}
#: jax's duration events -> the key each adds to, in ``stats()`` and, where
#: the event names the jitted function (the first three), in ``by_fn``
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
_SECONDS = dict.fromkeys(_DURATIONS.values(), 0.0)
#: fun_name -> {n, trace_s, lower_s, backend_compile_s}, every function
#: this process compiled (as many as its code has; ``stats()`` caps the table)
_BY_FN: dict = {}
#: rows of ``by_fn`` that ``stats()`` reports: those that cost most
BY_FN_ROWS = 24
_LOCK = threading.Lock()
_listening = False
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_nesting = threading.local()  # .heard: this thread's (began, seconds) not yet nested


def compile_cache_dir(platform: str) -> Optional[str]:
    """The directory code must set for a process computing on
    ``platform``, or None when code sets nothing (module doc)."""
    if os.environ.get(ENV_VAR) or platform == "cpu":
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        key = "requests"
    elif event == "/jax/compilation_cache/cache_hits":
        key = "hits"
    else:
        return
    with _LOCK:  # stats() reads from another thread
        _COUNTS[key] += 1


def _fn_name(name: str) -> str:
    """jax names a function ``make`` where it traces it and ``jit(make)``
    where it lowers and compiles it: one row for both."""
    m = _WRAPPED.match(name)
    return m.group(1) if m else name


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    fn = kw.get("fun_name")
    own = duration_secs
    if fn is not None:
        # jax times a function's trace around the traces of the jitted
        # functions it calls, and an eager op inside a trace compiles
        # there: events nest like the calls that made them, and an inner
        # one ends (and is heard) first.  The totals count each second
        # once: an event adds its duration less its direct children's,
        # which are the ones heard on this thread that began after it did.
        # (This listener runs as the event ends, so it began ``duration``
        # ago.)  ``by_fn`` keeps a function's WHOLE duration.
        heard = getattr(_nesting, "heard", None)
        if heard is None:
            heard = _nesting.heard = collections.deque(maxlen=512)
        began = time.perf_counter() - duration_secs
        while heard and heard[-1][0] >= began:
            own -= heard.pop()[1]
        heard.append((began, duration_secs))
        fn = _fn_name(str(fn))
    with _LOCK:
        _SECONDS[key] += max(own, 0.0)
        if fn is None:
            return
        row = _BY_FN.get(fn)
        if row is None:
            row = _BY_FN[fn] = {"n": 0, "trace_s": 0.0, "lower_s": 0.0,
                                "backend_compile_s": 0.0}
        row[key] += duration_secs
        if key == "backend_compile_s":  # one a program compiled or loaded
            row["n"] += 1
            _COUNTS["programs"] += 1


def ensure_compile_cache() -> None:
    """Apply the rule in this process (idempotent) and start counting
    cache requests, hits and compile seconds for ``stats()``."""
    import jax

    global _listening
    with _LOCK:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
    path = compile_cache_dir(jax.default_backend())
    if path is not None and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)


def totals() -> tuple:
    """(trace_s, lower_s, backend_compile_s, requests, hits) so far: what
    ``StepRunner`` reads on both sides of a site's first call."""
    with _LOCK:
        return (_SECONDS["trace_s"], _SECONDS["lower_s"], _SECONDS["backend_compile_s"],
                _COUNTS["requests"], _COUNTS["hits"])


def stats() -> dict:
    """Where this process's cache lives (None: no persistent cache), how
    many cacheable compiles it asked for / found there, and the seconds of
    every compile since ``ensure_compile_cache()`` as jax times them:
    ``trace_s``, ``lower_s``, ``backend_compile_s`` (the compiler, or the
    cache's load where it hit), each second counted once (an inner jit's
    trace not again under the outer's); of that load ``cache_retrieval_s``
    and what it ``saved_s``, over ``programs`` programs; ``by_fn`` has the
    first three by the jitted function's name, each function's whole
    duration, for the ``BY_FN_ROWS`` that cost most (those that own a
    program before those only traced inside another's)."""
    import jax

    with _LOCK:
        rows = sorted(  # functions that own a program first: a jnp helper
            _BY_FN.items(), reverse=True,  # traced inside one only fills up
            key=lambda kv: (kv[1]["n"] > 0, kv[1]["trace_s"] + kv[1]["lower_s"]
                            + kv[1]["backend_compile_s"]),
        )[:BY_FN_ROWS]
        return {
            "dir": jax.config.jax_compilation_cache_dir,
            **_COUNTS, **_SECONDS,
            "by_fn": {fn: dict(row) for fn, row in rows},
        }
