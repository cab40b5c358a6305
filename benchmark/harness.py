"""Shared pieces of the benchmark harness: the manifest and its data
files, the percentile rule, progress lines, process clean-up and the
loaders that find a traffic kind, an end-to-end metric or a per-layer
metric BY THE NAME ``BENCHMARK.json`` gives it.

Nothing here names a cell, a metric or a configuration.  Standard
library only: the process that imports this never opens a jax backend.
"""

from __future__ import annotations

import ctypes
import importlib
import importlib.util
import json
import math
import os
import signal
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")  # git-ignored, fixed path


class BenchFailure(Exception):
    """The run cannot give a result; the process exits non-zero."""


def note(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def emit(event: str, **fields) -> None:
    """One JSON progress line on stdout (never the last line)."""
    print(json.dumps({"event": event, **fields}, default=str), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


#: the driver stops a run that is still going after this many seconds, and
#: the slowest run of any cell (traced, from empty caches) aims under the
#: target (README, "What a run costs")
RUN_LIMIT_S = 360.0
RUN_TARGET_S = 300.0


class Budget:
    """Where one run's wall time went: consecutive parts from the process's
    start to the result line, so the parts sum to ``total_s``.  ``mark``
    closes the part that began at the previous mark, at an instant given
    or now (never before the previous mark, never in the future); a part
    marked twice adds up.  No metric reads it: the ``run_budget`` progress
    line is what the next cell's cost is sized from."""

    def __init__(self, t_start: float, clock=time.time):
        self.t_start = self._last = t_start
        self._clock = clock
        self.parts: dict = {}

    def mark(self, name: str, t: float | None = None) -> float:
        now = self._clock()
        t = now if t is None else min(max(t, self._last), now)
        self.parts[name] = self.parts.get(name, 0.0) + t - self._last
        self._last = t
        return t

    def line(self, **extra) -> dict:
        total = self.mark("other") - self.t_start
        return {"parts_s": dict(self.parts), "total_s": total,
                "limit_s": RUN_LIMIT_S, "target_s": RUN_TARGET_S, **extra}


# ---------------------------------------------------------------------------
# manifest and data files
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise BenchFailure(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in man['workloads']]}"
    )


def load_config(man: dict, name: str) -> dict:
    """The configuration's file as ``BENCHMARK.json`` names it; a name the
    manifest does not list (an ``--adhoc`` run) is ``configs/<name>.json``."""
    files = {c["name"]: c["file"] for c in man["configs"]}
    file = files.get(name, os.path.join("benchmark", "configs", f"{name}.json"))
    check(os.path.exists(os.path.join(ROOT, file)), f"no configuration {name!r}: {file}")
    cfg = load_json(os.path.join(ROOT, file))
    cfg["name"], cfg["file"] = name, file
    return cfg


def sizes(config: dict, rehearsal: bool) -> dict:
    """The configuration as it is run: the file's keys, and in a rehearsal
    its ``rehearsal`` block laid over them (tiny sizes for a CPU)."""
    return dict(config, **config["rehearsal"]) if rehearsal else config


def load_family(config: dict):
    """``families/<family>.py``: what ties the configuration's published
    keys to the program's model code and to its plain reference."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmark.families.{config['family']}")


def family_piece(config: dict, piece: str):
    fam = load_family(config)
    check(hasattr(fam, piece),
          f"families/{config['family']}.py defines no {piece!r}, which this run needs")
    return getattr(fam, piece)


def load_traffic(name: str) -> dict:
    """``traffic/<name>.json``; a file may name another under ``extends``
    (a cell's rate on top of the shared distribution) — its own keys win."""
    path = os.path.join(BENCH_DIR, "traffic", f"{name}.json")
    t = load_json(path)
    if "extends" in t:
        base = load_traffic(t["extends"])
        base.update({k: v for k, v in t.items() if k != "extends"})
        t = base
    t["name"] = name
    return t


def metrics_for(man: dict, section: str, workload: str) -> list:
    """The metrics of ``section`` this cell reports: those that list it
    under ``workloads`` or list nothing."""
    return [
        m for m in man[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error,
    never a default (a share of a guessed peak is not a measurement)."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    for name, row in table.items():
        if name.startswith("_"):
            continue
        if name.lower() in str(device_kind).lower():
            return row
    raise BenchFailure(
        f"no peaks known for device_kind {device_kind!r}; add its row, "
        "with a source, to benchmark/peaks.json"
    )


def _load_file_module(kind_dir: str, name: str):
    path = os.path.join(BENCH_DIR, kind_dir, f"{name}.py")
    check(os.path.exists(path), f"{kind_dir}/{name}.py does not exist")
    if os.path.dirname(path) not in sys.path:
        sys.path.insert(0, os.path.dirname(path))  # readers share _common.py
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind_dir}_{abs(hash(name))}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(section: str, name: str):
    """The reader of one metric: ``end_to_end/<name>.py`` or
    ``layer_metrics/<name>.py``, a module with ``read(run) -> number|None``."""
    kind_dir = "end_to_end" if section == "end_to_end" else "layer_metrics"
    return _load_file_module(kind_dir, name)


def load_kind(kind: str):
    """``traffic_kinds/<kind>.py`` as a real module of the package, so that
    a worker process can import what the kind hands to the program."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmark.traffic_kinds.{kind}")


def read_metrics(man: dict, section: str, workload: str, run: dict) -> dict:
    """name -> {"value", "unit"} for every metric of ``section`` this cell
    reports; a reader that finds nothing to read returns None and the
    metric is left out of the line."""
    out = {}
    for m in metrics_for(man, section, workload):
        value = load_metric(section, m["name"]).read(run)
        if value is None:
            note(f"{section} metric {m['name']}: nothing to read, left out")
            continue
        check(
            isinstance(value, (int, float)) and math.isfinite(value),
            f"{section} metric {m['name']} is not a finite number: {value!r}",
        )
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the sample at or below it.  No interpolation, so the result
    is always a value that was measured."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return vals[rank - 1]


def median(values) -> float:
    vals = sorted(values)
    if not vals:
        raise ValueError("median of an empty sample")
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else 0.5 * (vals[mid - 1] + vals[mid])


# ---------------------------------------------------------------------------
# environment and processes
# ---------------------------------------------------------------------------


def prepare_environment(rehearsal: bool = False) -> None:
    """Before ``ray_tpu.init()``: workers inherit all of it."""
    env = os.environ
    if not rehearsal:  # a CPU rehearsal compiles in seconds and caches nothing
        # the path is part of the cache key: fixed, inside the checkout
        env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CACHE_DIR, "jax"))
        # every compile is cached, not only those of a second or more
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
        os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    env.setdefault("RAY_TPU_EVENTS_CAPACITY", "65536")
    env.setdefault("TPU_LOG_DIR", "disabled")
    paths = [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(paths))
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def become_subreaper() -> None:
    """Orphans of anything this process starts re-parent HERE, so that
    ``reap_descendants`` can find and end them."""
    PR_SET_CHILD_SUBREAPER = 36
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)


def _descendants() -> list:
    """(pid, state) of every child and adopted orphan of this process."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        state, ppid = stat.rsplit(")", 1)[1].split()[:2]
        if int(ppid) == me:
            out.append((int(pid), state))
    return out


def _collect_dead() -> None:
    """Wait for every child that has already ended: what died is a zombie
    until someone waits for it, and on a machine whose init does not, it
    outlives this process."""
    while True:
        try:
            if os.waitpid(-1, os.WNOHANG)[0] == 0:
                return
        except ChildProcessError:
            return


def reap_descendants(grace_s: float = 10.0) -> list:
    """Wait ``grace_s`` for every child and adopted orphan to end, then
    kill what is left and wait for it — again and again until none is
    left: killing a parent hands ITS children to this process (the
    subreaper), and one that is still alive when this process exits goes
    to init and is a leftover there.  A child that shows as a zombie but
    cannot be collected yet is still ending (its first thread is gone, the
    others are giving back what the process held) and is waited for like
    any other.  Returns the pids that were killed."""
    deadline, killed = time.time() + grace_s, []
    while True:
        _collect_dead()
        left = _descendants()
        if not left:
            return killed
        if time.time() < deadline:
            time.sleep(0.1)
            continue
        for pid, state in left:
            if state == "Z":
                continue
            try:
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)
            except ProcessLookupError:
                pass
        for pid, _state in left:
            try:
                os.waitpid(pid, 0)
            except ChildProcessError:
                pass


#: device nodes of a TPU chip: a process that has one open holds the chip
CHIP_NODES = ("/dev/vfio/", "/dev/accel")


def chip_holders() -> dict:
    """pid -> command name of every process with a chip's device node
    open, by any of its threads (a process whose first thread has ended
    keeps its files until the last one has)."""
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            tasks = os.listdir(f"/proc/{pid}/task")
        except OSError:
            continue
        for tid in tasks:
            fds = f"/proc/{pid}/task/{tid}/fd"
            try:
                held = any(os.readlink(os.path.join(fds, fd)).startswith(CHIP_NODES)
                           for fd in os.listdir(fds))
            except OSError:
                continue
            if held:
                try:
                    with open(f"/proc/{pid}/comm") as f:
                        out[int(pid)] = f.read().strip()
                except OSError:
                    out[int(pid)] = "?"
                break
    return out


def wait_for_free_chips(timeout_s: float = 60.0) -> tuple:
    """Wait until no process holds a chip (a replica that has been told to
    end gives back four chips one after the other, for seconds, and a
    process that opens them meanwhile fails: "Device or resource busy").
    Returns (seconds waited, the holders seen first)."""
    t0, first = time.time(), None
    while True:
        holders = chip_holders()
        first = holders if first is None else first
        if not holders or time.time() - t0 > timeout_s:
            return time.time() - t0, first
        time.sleep(0.25)


def run_dir(workload: str, seed: int) -> str:
    """Scratch for one run's plan, records and trace: inside the checkout's
    git-ignored cache, emptied by the next run of the same cell and seed."""
    import shutil

    path = os.path.join(CACHE_DIR, "runs", f"{workload}-{seed}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
