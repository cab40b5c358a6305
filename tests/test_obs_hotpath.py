"""Telemetry hot-path contracts (PR 11 rebuild).

Three layers of proof that observability-ON costs the task plane nothing:

* **Static (lint fixture):** the emit paths — ``events.record``,
  ``Counter.inc`` / ``Gauge.set`` / ``Histogram.observe``, ``tracing.span``
  dispatch — acquire NO shared lock, verified against the real sources
  through the raylint phase-1 index (``trans_lock_acqs``), and the new
  events-collector drainer thread is visible to RL011's daemon-path
  analysis.
* **Concurrency stress:** N threads emitting events and bumping counters
  while the collector folds rings — no lost, duplicated, or
  reordered-within-thread events; the per-ring drop counter is EXACT
  under overflow (single-writer accounting, not the old advisory RMW).
* **Crash integrity:** a SIGTERM crash-flush fired mid-stream (emitters
  still running) writes a readable JSONL whose events are unique and
  in-order per thread.
"""

import ast
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import events
from ray_tpu.util import metrics as um
from ray_tpu.util import tracing


@pytest.fixture
def fresh_ring():
    st = events.stats()
    events.clear()
    events.set_enabled(True)
    yield
    events.configure(capacity=st["capacity"])
    events.set_enabled(st["enabled"])
    events.clear()


# ---------------------------------------------------------------------------
# static: the emit paths acquire no shared lock (raylint index fixture)
# ---------------------------------------------------------------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOT_PATHS = (
    ("ray_tpu/_private/events.py", "ray_tpu._private.events", "record"),
    ("ray_tpu/util/metrics.py", "ray_tpu.util.metrics", "Counter.inc"),
    ("ray_tpu/util/metrics.py", "ray_tpu.util.metrics", "Gauge.set"),
    ("ray_tpu/util/metrics.py", "ray_tpu.util.metrics", "Histogram.observe"),
    ("ray_tpu/util/tracing.py", "ray_tpu.util.tracing", "span"),
    # profiling plane (ISSUE 13): waterfall stamps ride every sampled
    # submit/dispatch/exec hop, and the step-profiler note() runs per
    # jitted call — all must stay lock-free like the paths above
    ("ray_tpu/util/waterfall.py", "ray_tpu.util.waterfall", "maybe_start"),
    ("ray_tpu/util/waterfall.py", "ray_tpu.util.waterfall", "stamp"),
    ("ray_tpu/util/device_prof.py", "ray_tpu.util.device_prof",
     "JitProfiler.note"),
    # object-plane flight deck (ISSUE 19): the core.object.* emit helper
    # rides every put/map/unmap/pull on the data plane, and the reader
    # pin ledger notes/drops a pin per zero-copy read — all lock-free
    ("ray_tpu/_private/events.py", "ray_tpu._private.events", "emit"),
    ("ray_tpu/_private/shm_store.py", "ray_tpu._private.shm_store",
     "note_pin"),
    ("ray_tpu/_private/shm_store.py", "ray_tpu._private.shm_store",
     "drop_pin"),
    # request latency attribution plane (ISSUE 20): the phase ledger is
    # charged on every admission / prefill chunk / decode step / preempt
    # under the engine lock's critical sections — the stamp itself must
    # acquire nothing (a list add + two float ops; the fold at finish
    # pays for assembly, never the per-step charge)
    ("ray_tpu/util/phases.py", "ray_tpu.util.phases", "new_ledger"),
    ("ray_tpu/util/phases.py", "ray_tpu.util.phases", "charge"),
    # the streamed token's stations (ISSUE 38): every station and leg
    # observes through a series bound once a stream (emit, wake, sent in
    # the replica's loop and handler threads, acked / written / head_hold
    # in its recv loop), and the proxy's stream thread hands its write
    # gaps to the generator once an item — none may take a lock
    ("ray_tpu/util/metrics.py", "ray_tpu.util.metrics",
     "BoundHistogram.observe"),
    ("ray_tpu/_private/runtime.py", "ray_tpu._private.runtime",
     "ObjectRefGenerator.report_delivered"),
)


def _real_index():
    from ray_tpu._lint.core import FileContext
    from ray_tpu._lint.index import build_index

    ctxs = []
    for rel in sorted({p for p, _m, _q in HOT_PATHS}):
        path = os.path.join(REPO, rel)
        text = open(path).read()
        ctxs.append(FileContext(path, rel, text, ast.parse(text)))
    return build_index(ctxs, display_root=REPO)


def test_emit_paths_acquire_no_shared_lock():
    """The zero-cost contract, mechanized: every hot-path function must
    reach ZERO lock acquisitions through the whole-program call graph.
    A lock creeping back into record()/inc()/set()/observe()/span() —
    directly or via a helper — fails here, naming the acquisition."""
    idx = _real_index()
    for _rel, module, qualname in HOT_PATHS:
        info = idx.functions.get(f"{module}:{qualname}")
        assert info is not None, f"index lost {module}:{qualname}"
        acqs = idx.trans_lock_acqs(info)
        assert not acqs, (
            f"telemetry hot path {module}:{qualname} acquires lock(s): "
            f"{sorted(a[0] for a in acqs)} — the emit path must stay "
            "lock-free (OBSERVABILITY.md hot-path architecture)"
        )


def test_collector_thread_visible_to_daemon_analysis():
    """RL011 coverage of the new drainer: the events-collector thread
    target must be in the index's daemon-reachable set so
    blocking-under-lock analysis applies to everything it calls."""
    idx = _real_index()
    daemon = idx.daemon_reachable()
    keys = {getattr(k, "key", k) for k in daemon}
    assert any("_collector_loop" in str(k) for k in keys), (
        "events._collector_loop is not daemon-reachable in the index — "
        "RL011 cannot see the drainer thread"
    )


def _full_tree_index():
    """Whole-package index (cached): the LOCKFREE verification needs the
    real thread roots, which span head/worker/serve modules."""
    global _FULL_IDX
    try:
        return _FULL_IDX
    except NameError:
        pass
    import pathlib

    from ray_tpu._lint.core import FileContext, iter_python_files
    from ray_tpu._lint.index import build_index

    # iter_python_files is the SAME collector the lint gate uses (skip
    # dirs, display paths) — this test must analyze exactly what the
    # self-lint run analyzes
    root = pathlib.Path(REPO)
    ctxs = []
    for abs_path, display in iter_python_files(
        [root / "ray_tpu"], display_root=root
    ):
        text = abs_path.read_text()
        ctxs.append(FileContext(abs_path, display, text, ast.parse(text)))
    _FULL_IDX = build_index(ctxs, display_root=root)
    return _FULL_IDX


def test_lockfree_declarations_verified_against_real_sources():
    """The RL017 contract, index-backed like the zero-lock test above:
    every LOCKFREE entry in the tree matches accessed state, every BARE
    entry really is single-writer (≤1 writing thread root in the whole-
    program thread model), and every ':atomic' entry has no
    read-modify-write site. A declaration drifting from the code fails
    tier-1 here AND in the self-lint gate — by construction, since this
    re-runs the verifier the lint gate uses."""
    from ray_tpu._lint import concurrency

    idx = _full_tree_index()
    model = concurrency.get_model(idx)
    decls = idx.lockfree_decls()
    assert decls, "the tree lost its LOCKFREE declarations"
    entries = [
        (module, e) for module, es, _n, _c in decls for e in es
    ]
    # the PR 11 hot-path declarations specifically must exist
    assert any(e.startswith("_rings") for _m, e in entries)
    checked = 0
    for module, entry in entries:
        key, qual = concurrency.parse_lockfree(entry)
        if "." not in key:
            key = f"{module}.{key}"
        states = model.by_display.get(key)
        assert states, f"LOCKFREE entry {entry!r} matches no accessed state"
        accs = [a for st in states for a in model.accesses[st]]
        writes = [a for a in accs if a.kind in ("store", "aug", "mutate")]
        if qual is None:
            wroots = {a.root for a in writes}
            assert len(wroots) <= 1, (
                f"bare LOCKFREE entry {entry!r} is written from "
                f"{sorted(wroots)} — no longer single-writer"
            )
        else:
            assert qual == "atomic", entry
            bad = [a for a in writes if a.kind == "aug"]
            assert not bad, (
                f"':atomic' LOCKFREE entry {entry!r} has a "
                "read-modify-write site"
            )
        checked += 1
    assert checked >= 8  # head, events, worker_main, waterfall, ... all in


# ---------------------------------------------------------------------------
# concurrency stress: no lost / duplicated / reordered-within-thread events
# ---------------------------------------------------------------------------


def _emit(etype, thread_idx, n):
    for i in range(n):
        events.record(etype, t=thread_idx, i=i)


def test_threads_no_lost_dup_reorder(fresh_ring):
    events.configure(capacity=8192)
    n_threads, per = 8, 1500
    threads = [
        threading.Thread(
            target=_emit, args=("stress.a", k, per), name=f"obs-stress-{k}"
        )
        for k in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    evs = [e for e in events.snapshot() if e["type"] == "stress.a"]
    assert len(evs) == n_threads * per  # nothing lost
    assert len({e["seq"] for e in evs}) == len(evs)  # nothing duplicated
    # snapshot is globally seq-ordered, and within each emitting thread
    # the payload order must match emission order exactly
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs)
    per_thread = {k: [] for k in range(n_threads)}
    for e in evs:
        per_thread[e["t"]].append(e["i"])
    for k, idxs in per_thread.items():
        assert idxs == list(range(per)), f"thread {k} reordered/lost events"


def test_drop_counter_exact_on_overflow(fresh_ring):
    events.configure(capacity=64)
    n_threads, per = 4, 500
    threads = [
        threading.Thread(
            target=_emit, args=("stress.b", k, per), name=f"obs-drop-{k}"
        )
        for k in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    rows = {
        r["thread"]: r for r in events.ring_stats() if r["thread"].startswith("obs-drop-")
    }
    assert len(rows) == n_threads
    for name, r in rows.items():
        # single-writer accounting: EXACTLY emitted - capacity dropped,
        # and the ring holds exactly the newest `capacity`
        assert r["dropped"] == per - 64, (name, r)
        assert r["size"] == 64, (name, r)
    # each surviving window is the newest 64 of its thread, in order
    evs = [e for e in events.snapshot() if e["type"] == "stress.b"]
    per_thread = {}
    for e in evs:
        per_thread.setdefault(e["t"], []).append(e["i"])
    for k, idxs in per_thread.items():
        assert idxs == list(range(per - 64, per)), f"thread {k} kept wrong window"


def test_collector_folds_dead_thread_rings(fresh_ring):
    events.configure(capacity=256)
    stats0 = events.stats()
    threads = [
        threading.Thread(
            target=_emit, args=("stress.c", k, 50), name=f"obs-fold-{k}"
        )
        for k in range(5)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    rings_before = events.stats()["rings"]
    events.collector_pass_for_tests()
    st = events.stats()
    # the dead threads' rings are gone, their events are not
    assert st["rings"] <= rings_before - 5
    evs = [e for e in events.snapshot() if e["type"] == "stress.c"]
    assert len(evs) == 5 * 50
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    assert st["dropped"] == stats0["dropped"]  # folding drops nothing here


def test_events_dropped_metric_published(fresh_ring):
    events.configure(capacity=16)

    t = threading.Thread(
        target=_emit, args=("stress.d", 0, 116), name="obs-metric-drop"
    )
    t.start()
    t.join(timeout=60)
    events.collector_pass_for_tests()
    # the lazy counter exists and carries (at least) this test's 100 drops
    drop_counters = [
        m for m in um._registry if m.name == "events_dropped"
    ]
    assert drop_counters, "events_dropped counter was never created"
    total = sum(
        v for m in drop_counters for v in m._snapshot()["data"].values()
    )
    assert total >= 100


def test_counter_concurrent_exact():
    c = um.Counter("obs_hotpath_exact_total", "stress", tag_keys=("lane",))
    n_threads, per = 8, 5000

    def bump(k):
        for _ in range(per):
            c.inc(1.0, tags={"lane": str(k % 2)})

    threads = [threading.Thread(target=bump, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    data = c._snapshot()["data"]
    total = sum(data.values())
    # thread-local cells are single-writer: the merge is EXACT, no lost
    # increments despite zero locks on the inc path
    assert total == n_threads * per
    assert data['{"lane":"0"}'] == data['{"lane":"1"}']


def test_dead_thread_cells_compact_without_losing_counts():
    """Thread churn (serve's per-stream proxy threads) must not leak
    metric cells: dead threads' cells fold into the base data at
    snapshot time — totals exactly preserved, cell list shrunk."""
    c = um.Counter("obs_hotpath_churn_total", "stress")
    for wave in range(3):
        threads = [
            threading.Thread(target=lambda: [c.inc() for _ in range(100)])
            for _ in range(10)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert sum(c._snapshot()["data"].values()) == (wave + 1) * 1000
    # after the folds, the dead threads' cells are gone (only cells of
    # still-alive threads — e.g. this one's, if it ever emitted — remain)
    assert len(c._cells) <= 1
    assert sum(c._snapshot()["data"].values()) == 3000


def test_histogram_concurrent_exact():
    h = um.Histogram(
        "obs_hotpath_exact_hist_s", "stress", boundaries=(0.1, 1.0)
    )
    n_threads, per = 6, 3000

    def observe():
        for i in range(per):
            h.observe(0.05 if i % 2 else 5.0)

    threads = [threading.Thread(target=observe) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    p = h.percentiles()
    assert p["count"] == n_threads * per


def test_unsampled_context_records_nothing(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "0")
    tracing.clear()
    with tracing.trace_context() as rid:
        assert tracing.current_request_id() == rid
        with tracing.span("invisible", x=1):
            pass
    assert not any(s["name"] == "invisible" for s in tracing.get_spans())
    # the context ships AS THE TOKEN (by reference): forensics keep the
    # request id downstream, the sampling decision is pinned (no
    # half-sampled traces), and spans stay free everywhere
    ctx = tracing.mint_context()
    assert type(ctx) is tracing.UnsampledContext
    assert tracing.context_for_spec(ctx) is ctx
    import pickle

    clone = pickle.loads(pickle.dumps(ctx))  # rides task specs
    assert clone.request_id == ctx.request_id and not clone.sampled
    # a lazy root whose id lands unsampled also ships a token
    lazy = tracing.task_context(None, b"\x00" * 16)
    assert type(tracing.context_for_spec(lazy)) is tracing.UnsampledContext
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "1")
    with tracing.trace_context():
        with tracing.span("visible"):
            pass
    assert any(s["name"] == "visible" for s in tracing.get_spans())


def test_waterfall_unsampled_path_costs_like_disabled_record():
    """The satellite pin: an UNSAMPLED task's waterfall cost (one type
    check in maybe_start) must stay in the same class as a disabled
    record() — the cheapest thing the telemetry plane knows how to do.
    Generous multiplier: this box's timing noise is ±30%, the contract
    is about orders of magnitude (a lock or an allocation creeping into
    the unsampled path shows up as 10-100x, not 3x)."""
    from ray_tpu.obs import measure_overhead

    res = measure_overhead(n=30_000)
    budget = max(res["event_record_disabled_ns"] * 5, 1_000.0)
    assert res["waterfall_unsampled_ns"] <= budget, res
    # sampled stamps are clock+append — same class as a counter inc
    assert res["waterfall_stamp_ns"] <= max(
        res["counter_inc_ns"] * 10, 5_000.0
    ), res
    # the step profiler emits a tagged observe + a cache-size read per
    # jitted call (ms-scale steps): must stay well under 100us
    assert res["device_prof_note_ns"] <= 100_000.0, res


def test_waterfall_maybe_start_only_stamps_sampled_dicts():
    from ray_tpu.util import waterfall as wfl

    assert wfl.maybe_start(None) is None
    assert wfl.maybe_start(tracing.UnsampledContext("ab")) is None
    lazy = tracing.LazyTaskContext(b"\x01" * 16)
    assert wfl.maybe_start(lazy) is None  # rootless ships nothing
    wf = wfl.maybe_start({"request_id": "ab"})
    assert isinstance(wf, list) and len(wf) == 1
    wfl.stamp(wf)
    assert len(wf) == 2 and wf[1] >= wf[0]


def test_lazy_task_context_materializes_on_demand():
    task_id = bytes(range(16))
    ctx = tracing.task_context(None, task_id)
    assert type(ctx) is tracing.LazyTaskContext
    assert ctx._rid is None  # nothing paid yet
    rid = ctx.request_id
    assert rid == task_id.hex()[:16]
    assert ctx.get("request_id") == rid
    # a shipped context is returned as-is (by reference, no copy)
    shipped = {"request_id": "abc123"}
    assert tracing.task_context(shipped, task_id) is shipped
    assert tracing.context_for_spec(shipped) is shipped


# ---------------------------------------------------------------------------
# SIGTERM crash-flush fired mid-stream
# ---------------------------------------------------------------------------


def test_sigterm_crash_flush_mid_stream(tmp_path):
    """Emitters on several threads are mid-append when SIGTERM lands on
    the main thread: the flush must still write every thread's ring as
    one seq-ordered JSONL — unique seqs, per-thread order intact — with
    the drop accounting in the header."""
    code = (
        "import os, signal, threading, time\n"
        "from ray_tpu._private import events\n"
        "events.configure(capacity=512)\n"
        "events.install_crash_handlers()\n"
        "stop = False\n"
        "def emit(k):\n"
        "    i = 0\n"
        "    while not stop:\n"
        "        events.record('mid.stream', t=k, i=i)\n"
        "        i += 1\n"
        "for k in range(4):\n"
        "    threading.Thread(target=emit, args=(k,), daemon=True).start()\n"
        "time.sleep(0.5)\n"
        "events.record('mid.main')\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
    )
    env = dict(os.environ, RAY_TPU_EVENTS_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, timeout=60,
        capture_output=True, cwd=REPO,
    )
    assert proc.returncode != 0  # died by the signal
    files = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert len(files) == 1, (files, proc.stderr.decode()[-500:])
    lines = [json.loads(x) for x in open(tmp_path / files[0])]
    header, evs = lines[0], lines[1:]
    assert header["reason"] == "sigterm"
    assert header["rings"] >= 4
    types = {e["type"] for e in evs}
    assert "mid.stream" in types and "crash.sigterm" in types
    seqs = [e["seq"] for e in evs]
    assert len(set(seqs)) == len(seqs)  # no duplicates across rings
    assert seqs == sorted(seqs)  # global emission order
    per_thread: dict = {}
    for e in evs:
        if e["type"] == "mid.stream":
            per_thread.setdefault(e["t"], []).append(e["i"])
    assert len(per_thread) == 4
    for k, idxs in per_thread.items():
        # each thread's surviving window is contiguous and in order
        assert idxs == list(range(idxs[0], idxs[0] + len(idxs))), (
            f"thread {k} events reordered or lost inside the flush"
        )
