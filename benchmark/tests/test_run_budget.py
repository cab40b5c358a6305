"""The run's time budget (ISSUE 31): the traced slice keeps its planned
length whatever the engine's lock does, it is bounded in work as well as
in seconds, a ``run_budget`` line's parts sum to its total, and every
``workloads`` list of the manifest names cells that exist.  Fakes on a
CPU: no chip, no sleep over a second."""

import os
import time

import pytest

from benchmark import harness as H
from benchmark import serving


class _Answer:
    """What ``.remote()`` gives back at once; ``result()`` takes ``wait_s``."""

    def __init__(self, value, wait_s=0.0, done=None):
        self._value, self._wait_s, self._done = value, wait_s, done

    def result(self, timeout=None):
        time.sleep(self._wait_s)
        if self._done:
            self._done()
        return self._value


class _Method:
    def __init__(self, fn):
        self.remote = fn


class FakeHandle:
    """A replica whose ``stats()`` answers only after ``lock_wait_s`` (the
    engine's lock) and whose ``stop_trace`` takes ``stop_s``."""

    def __init__(self, lock_wait_s, stop_s=0.05):
        self.calls, self.steps = [], 0
        self.stats = _Method(self._stats)
        self.start_trace = _Method(lambda d: self._call("start_trace", 0.02))
        self.stop_trace = _Method(lambda: self._call("stop_trace", stop_s))
        self._lock_wait_s = lock_wait_s

    def _stats(self):
        self.calls.append(("stats_asked", time.time()))
        self.steps += 10
        return _Answer({"steps": self.steps, "t_read": time.time() + self._lock_wait_s,
                        "hbm": {}}, self._lock_wait_s)

    def _call(self, name, seconds):
        self.calls.append((name + "_called", time.time()))
        return _Answer(None, seconds,
                       lambda: self.calls.append((name + "_returned", time.time())))


@pytest.mark.parametrize("lock_wait_s", [0.0, 0.4])
def test_a_blocking_stats_does_not_stretch_the_traced_slice(lock_wait_s, tmp_path):
    handle, span = FakeHandle(lock_wait_s, stop_s=0.3), 0.2
    join = serving.take_slice(handle, str(tmp_path), time.time(), span)
    asked = time.time()
    readings, timing = join()
    at = dict(handle.calls)
    # the slice function came back once the profiler was ASKED to stop
    assert asked < at["stop_trace_returned"] and timing["stop_call_s"] >= 0.3
    traced = at["stop_trace_called"] - at["start_trace_returned"]
    assert span <= traced < span + 0.1
    assert abs(timing["traced_s"] - traced) < 0.05 and timing["planned_s"] == span
    # both readings came back, the later one from the later call
    assert readings["trace_stop"]["steps"] > readings["trace_start"]["steps"]
    # and were waited for only after the profiler had stopped
    assert timing["waited_for_stop_s"] >= 0.25
    names = [n for n, _ in handle.calls]
    assert names.index("stats_asked") < names.index("start_trace_called")
    assert names.count("stats_asked") == 2


def test_the_slice_waits_for_its_beginning_on_the_clock_it_is_given():
    now, slept = [100.0], []

    def sleep(s):
        slept.append(s)
        now[0] += s

    handle = FakeHandle(0.0, stop_s=0.0)
    handle.start_trace = _Method(lambda d: _Answer(None))
    handle.stop_trace = _Method(lambda: _Answer(None))
    _, timing = serving.take_slice(handle, "d", 103.0, 2.5, clock=lambda: now[0], sleep=sleep)()
    assert slept[:2] == [3.0, 2.5] and timing["traced_s"] == 2.5


@pytest.mark.parametrize("steps_per_s,want", [
    (None, 3.0), (0.0, 3.0), (25.0, 3.0), (50.0, 3.0), (100.0, 1.5), (300.0, 0.5),
])
def test_the_slice_shortens_with_the_steps_a_second_and_never_passes_trace_s(steps_per_s, want):
    traffic = {"trace_s": 3.0}
    got = serving.slice_seconds(traffic, 50.0, steps_per_s)
    assert got == pytest.approx(want) and got <= traffic["trace_s"]
    # a quarter of a short window bounds it too
    assert serving.slice_seconds(traffic, 4.0, steps_per_s) <= 1.0
    if steps_per_s:
        assert got * steps_per_s <= serving.TRACE_STEPS + 1e-9


def test_the_slice_begins_where_the_plan_has_most_requests_due_just_before():
    # one request a second, and a burst of four due at 23.0-23.3
    dues = [float(i) for i in range(40)] + [23.0, 23.1, 23.2, 23.3]
    b = serving.slice_begin(dues, 20.0, 3.0)
    assert 20.0 <= b <= 20.0 + serving.TRACE_REACH_S
    assert b - serving.TRACE_WAIT_S <= 23.0 and 23.3 < b + 3.0 - 1.0
    # a closed loop's plan has no due instants: the nominal instant
    assert serving.slice_begin([], 20.0, 3.0) == 20.0
    # the plan alone decides: the same dues, the same slice
    assert serving.slice_begin(dues, 20.0, 3.0) == b


def test_steps_a_second_come_from_the_instants_the_engine_read_at():
    first, later = {"steps": 100, "t_read": 10.0}, {"steps": 400, "t_read": 20.0}
    assert serving.steps_per_second(first, later) == 30.0
    assert serving.steps_per_second(first, first) is None
    assert serving.steps_per_second(first, {"steps": 130, "t_read": 10.5}) is None


def test_the_engines_own_chunk_share_is_prompt_tokens_over_chunk_size_over_steps():
    counters = {"open": {"steps": 100, "prefill_tokens_computed": 1000},
                "close": {"steps": 1100, "prefill_tokens_computed": 1000 + 128 * 100}}
    assert serving.chunk_step_share(counters, 128) == pytest.approx(10.0)
    assert serving.chunk_step_share({"open": counters["open"], "close": counters["open"]}, 128) is None


def test_a_run_budget_lines_parts_sum_to_its_total():
    now = [50.0]
    b = H.Budget(50.0, clock=lambda: now[0])
    for name, dt in (("ray_init", 1.25), ("serve_run", 40.5), ("window", 50.0),
                     ("window", 0.5), ("shutdown", 3.0)):
        now[0] += dt
        b.mark(name)
    b.mark("lead_in", 10.0)  # an instant before the last mark adds nothing
    now[0] += 0.75
    line = b.line(reference={"cached": True})
    assert line["parts_s"]["window"] == 50.5 and line["parts_s"]["lead_in"] == 0.0
    assert sum(line["parts_s"].values()) == pytest.approx(line["total_s"]) == pytest.approx(96.0)
    assert line["limit_s"] == 360.0 and line["target_s"] == 300.0 < line["limit_s"]
    assert line["reference"] == {"cached": True}


def _metrics():
    man = H.manifest()
    return [(s, m["name"]) for s in ("end_to_end", "per_layer") for m in man[s]]


@pytest.mark.parametrize("section,name", _metrics())
def test_every_workloads_list_names_cells_that_exist_with_their_traffic(section, name):
    man = H.manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    metric = next(m for m in man[section] if m["name"] == name)
    where = metric.get("workloads")
    assert where is None or (where and len(set(where)) == len(where))
    for cell in where or cells:
        assert cell in cells, f"{name} lists {cell}, which is no cell"
        path = os.path.join(H.BENCH_DIR, "traffic", cells[cell]["traffic"] + ".json")
        assert os.path.exists(path), path
        traffic = H.load_traffic(cells[cell]["traffic"])
        assert "rate" in traffic or traffic["kind"] != "open_loop"
    assert "gptj_chat_r80" not in (where or [])


def test_the_reference_job_reads_a_cached_verdict_without_a_child(tmp_path, monkeypatch):
    started = []
    monkeypatch.setattr(H, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(serving.subprocess, "Popen", lambda *a, **k: started.append(a) or "child")
    config = {"name": "c", "correctness": {}}
    job = serving.ReferenceJob(config, [{"prompt": [1]}], [[2]], rehearsal=False)
    assert job.proc == "child" and len(started) == 1
    with open(job.path, "w") as f:
        f.write('{"ok": true, "max_deficit": 0.0}')
    again = serving.ReferenceJob(config, [{"prompt": [1]}], [[2]], rehearsal=False)
    assert again.proc is None and len(started) == 1
    assert again.verdict() == {"ok": True, "max_deficit": 0.0, "cached": True, "seconds": 0.0}
