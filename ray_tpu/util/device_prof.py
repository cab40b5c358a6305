"""Device-step profiler: per-jit-site wall time + runtime retrace detection.

Every jitted entry point in the serving/training hot loop (decode /
prefill / verify / fork in ``llm.model_runner``, the train step in
``train.trainer``) is supposed to trace ONCE per static shape and then
run from cache forever — that is the static-shape discipline the whole
engine is built on, and raylint RL014 (retrace-storm) enforces it
statically.  This module is RL014's **runtime twin**: it measures the
host wall time of each call into a per-site histogram (the ENQUEUE of an
asynchronous dispatch, not the device's step: ``device_step_seconds`` is
launch time plus any compile) and watches the jit
cache size (``PjitFunction._cache_size``) so a site that RECOMPILES
after its warmup baseline emits a ``<family>.retrace`` flight-recorder
event and bumps the ``device_retraces`` counter — which the
``retrace-storm`` SLO rule (``util.slo``) turns into a firing alert.

Usage (one profiler per owner, so two engines in one process never
compare cache sizes of different function objects)::

    prof = JitProfiler(event="llm.retrace")
    t0 = time.perf_counter()
    out = self._decode(...)
    prof.note("decode", self._decode, time.perf_counter() - t0)

``note`` is an EMIT PATH under the PR-11 zero-cost contract: a dict
probe, one lock-free histogram observe, and a C-level cache-size read —
no shared locks (``tests/test_obs_hotpath.py`` extends the index-backed
lint fixture over it).  The retrace branch (event + counter) only runs
when a site actually recompiled, which steady-state engines never do.

The first ``note`` per site sets the baseline — by construction that is
the warmup call (``LLMEngine.warmup`` / the first train step), so
legitimate cold compiles never count as retraces.  A site whose shapes
genuinely vary (none should) fires exactly once per NEW trace: the
baseline advances to the observed cache size each time.
"""

from __future__ import annotations

import threading
from typing import Optional

#: raylint RL012 registry.  The retrace EVENT types are per-owner
#: (``JitProfiler(event="llm.retrace" | "train.retrace")``) — a dynamic
#: ``record(self.event, ...)`` site RL012 deliberately skips — and are
#: documented in OBSERVABILITY.md's event-family tables instead.
METRIC_NAMES = (
    "device_step_seconds",
    "device_retraces",
)

_METRICS = None
_METRICS_LOCK = threading.Lock()

#: boundaries spanning sub-ms cached dispatch through multi-second compiles
_STEP_BOUNDARIES = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


def _metrics() -> dict:
    global _METRICS
    if _METRICS is not None:
        return _METRICS
    with _METRICS_LOCK:
        if _METRICS is not None:
            return _METRICS
        from ray_tpu.util.metrics import Counter, Histogram

        _METRICS = {
            "seconds": Histogram(
                "device_step_seconds",
                "HOST wall time of the call that ENQUEUES a jitted entry "
                "point (decode/prefill/verify/fork/train_step): dispatch is "
                "asynchronous, so this is launch time (plus any compile), "
                "not the device's step time — for that read a trace "
                "(decode_step_dev_ms) or the llm.step.*_launch/_fetch spans",
                boundaries=_STEP_BOUNDARIES,
                tag_keys=("site",),
            ),
            "retraces": Counter(
                "device_retraces",
                "jit sites that recompiled AFTER their warmup baseline — "
                "RL014's runtime twin; any nonzero rate trips the "
                "retrace-storm SLO rule",
                tag_keys=("site",),
            ),
        }
    return _METRICS


def _cache_size(fn) -> Optional[int]:
    """Compiled-executable count of a jitted callable, or None when the
    object doesn't expose one (plain callables in tests, future jax)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


class JitProfiler:
    """Per-owner step profiler.  ``note`` is the hot path; everything
    else (``stats``) is query-side."""

    __slots__ = ("event", "_sites", "_m")

    def __init__(self, event: str = "llm.retrace"):
        #: flight-recorder event type emitted on a retrace (``llm.retrace``
        #: for the serving engine, ``train.retrace`` for the train step)
        self.event = event
        # site -> [baseline cache size (None until known), calls, retraces];
        # single-writer in practice (the engine step / train loop thread),
        # and a racy double-count would only over-report — never a lock
        self._sites: dict[str, list] = {}
        self._m = _metrics()

    def note(self, site: str, fn, dur_s: float) -> bool:
        """Record one call of jit site ``site``; returns True when the
        call RETRACED an already-baselined site."""
        self._m["seconds"].observe(dur_s, tags={"site": site})
        st = self._sites.get(site)
        size = _cache_size(fn)
        if st is None:
            # first call per site == the warmup/compile call: baseline
            # here.  The zero-inc materializes the site's tagged series
            # BEFORE any retrace can happen — a window delta needs a
            # pre-storm sample to diff against, so without it the first
            # storm of a site would never trip the retrace-storm SLO
            self._sites[site] = [size, 1, 0]
            self._m["retraces"].inc(0.0, tags={"site": site})
            return False
        st[1] += 1
        if size is None or st[0] is None or size <= st[0]:
            if st[0] is None:
                st[0] = size
            return False
        # recompile after warmup: advance the baseline so each NEW trace
        # fires exactly once, then take the (cold) reporting path
        st[0] = size
        st[2] += 1
        self._m["retraces"].inc(tags={"site": site})
        from ray_tpu._private import events as _events

        _events.record(
            self.event, site=site, cache_size=size,
            call_n=st[1], dur_s=round(dur_s, 6),
        )
        return True

    def stats(self) -> dict:
        """Per-site ``{"calls", "retraces", "cache_size"}`` (query side)."""
        return {
            site: {"cache_size": st[0], "calls": st[1], "retraces": st[2]}
            for site, st in self._sites.items()
        }

    @property
    def retraces(self) -> int:
        return sum(st[2] for st in self._sites.values())


def mosaic_kernels(lowered) -> list:
    """Names of the Mosaic (compiled Pallas) kernels inside a lowered jit
    program: each is a ``tpu_custom_call`` carrying its kernel function's
    name.  An INTERPRETED ``pallas_call`` lowers to plain ops and
    contributes nothing — an empty list where a kernel was expected means
    the step does not run the kernel on the device."""
    import re

    return re.findall(
        r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"', lowered.as_text()
    )


def device_report() -> dict:
    """What THIS process computes on, as jax reports it: platform,
    device_kind, device count, the jax/jaxlib/libtpu versions, each
    device's ``memory_stats()`` (where the backend reports one), the
    persistent compile cache's location and hit count with the seconds
    this process spent compiling, and its start-up ledger
    (``_private.startup``), so that a serve replica and a train worker
    answer alike.  Initializes the
    backend — call it only in a process that computes (a serve replica,
    a train worker), never in a driver that must leave the chip free."""
    import importlib.metadata

    import jax
    import jaxlib

    from ray_tpu._private import compile_cache, startup

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    memory = {}
    for d in devices:
        stats = d.memory_stats() or {}
        memory[str(d.id)] = {
            k: stats[k]
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                      "peak_bytes_reserved", "bytes_limit")
            if k in stats
        }
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
        "memory": memory,
        "compile_cache": compile_cache.stats(),
        "startup": startup.report(),
    }
