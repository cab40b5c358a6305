"""From a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.  Needs jax for ``ProfileData`` only; opens no
backend.

The reduced trace is a plain dict:

``window_s``      length of the traced slice: first to last device event
``busy_s``        union of device-op intervals, averaged over the chips
``devices``       per chip: ``busy_s``, ``ops`` (name -> [count, seconds]),
                  ``programs`` (name -> list of durations in seconds),
                  ``gaps`` (longest idle gaps: [start_s, seconds, label])
``host_spans``    name -> [count, seconds] of host events the benchmark's
                  own loop wrote (names starting with ``bench:``)

Device planes are the planes named ``/device:TPU:<n>``.  On such a plane
the line "XLA Ops" holds one event per executed HLO op or kernel and the
line "XLA Modules" one per executed program; only those two are read.
Ops nest (a ``while`` holds its body's ops), so busy time is a UNION of
intervals and time by op name counts leaves only: an op that wholly
contains later ops is a container and is left out of ``ops``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(
    r"^%?(?P<op>[\w.\-]+) = (?P<shape>\(?[a-z0-9]+\[[\d,]*\])[^ ]*"
    r"(?:, [^ ]+)*\)? (?P<code>[\w\-]+)\("
)
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO text (hundreds of
    characters).  Keep what identifies it: ``fusion.4 f32[1612800] fusion``,
    and for a custom call its target (``tpu_custom_call`` is a Pallas
    kernel)."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    out = f"{m['op']} {m['shape'].lstrip('(')} {m['code']}"
    t = _TARGET.search(name)
    return f"{out} {t.group(1)}" if t else out


def read_planes(path: str) -> dict:
    """plane name -> line name -> [(name, start_ns, duration_ns), ...],
    device op names shortened by ``short_name``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            shorten = plane.name.startswith("/device:") and line.name == OPS_LINE
            for e in line.events:
                name = short_name(e.name) if shorten else e.name
                evs.append((name, float(e.start_ns), float(e.duration_ns)))
    return out


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def leaf_ops(events) -> list:
    """Events that contain no later-starting event: the ops that did the
    work.  ``events`` are (name, start, duration)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (name, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d + 1e-3 and d > 0:
            continue  # a container: the next event starts and ends inside it
        leaves.append((name, s, d))
    return leaves


def _program_before(t: float, modules: list) -> str:
    """The program that ended last before ``t``."""
    done = [m for m in modules if m[1] <= t + 1.0]
    return max(done, key=lambda m: m[1])[2] if done else "?"


def _label_gap(start: float, end: float, spans: list, otherwise: str) -> str:
    """The benchmark's own host span that covers most of an idle gap; where
    there is none (the program carries no annotations yet), the program
    the device had just finished."""
    best, best_cover = f"unattributed: {otherwise}", 0.0
    for name, s, d in spans:
        cover = min(end, s + d) - max(start, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def program_name(event_name: str) -> str:
    """``jit__decode_impl(1234567)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_planes(planes: dict, top: int = 10) -> dict:
    spans = [
        ev for lines in planes.values() for evs in lines.values() for ev in evs
        if ev[0].startswith(SPAN_PREFIX)
    ]
    devices = {}
    for pname in sorted(planes):
        if not re.fullmatch(r"/device:TPU:\d+", pname):
            continue
        ops = planes[pname].get(OPS_LINE, [])
        if not ops:
            continue
        t_first = min(s for _, s, _ in ops)
        t_last = max(s + d for _, s, d in ops)
        merged = sorted((s, s + d) for _, s, d in ops)
        gaps, cur_end = [], merged[0][1]
        for s, e in merged[1:]:
            if s > cur_end:
                gaps.append((cur_end, s))
            cur_end = max(cur_end, e)
        modules = sorted(
            (s, s + d, program_name(n)) for n, s, d in planes[pname].get(MODULES_LINE, [])
        )
        mod_starts = [m[0] for m in modules]

        def program_at(t: float, modules=modules, mod_starts=mod_starts) -> str:
            i = bisect.bisect_right(mod_starts, t) - 1
            return modules[i][2] if i >= 0 and t < modules[i][1] else "?"

        # two programs may both hold a "fusion.4": an op is named by the
        # program that was running when it started
        by_name: dict = {}
        for name, s, d in leaf_ops(ops):
            row = by_name.setdefault(f"{program_at(s)}/{name}", [0, 0.0])
            row[0] += 1
            row[1] += d * 1e-9
        programs: dict = {}
        for _s, _e, name in modules:
            programs.setdefault(name, []).append((_e - _s) * 1e-9)
        gaps.sort(key=lambda g: g[0] - g[1])
        devices[pname] = {
            "window_s": (t_last - t_first) * 1e-9,
            "busy_s": union_seconds(merged) * 1e-9,
            "ops": by_name,
            "programs": programs,
            "gaps": [
                [(s - t_first) * 1e-9, (e - s) * 1e-9,
                 _label_gap(s, e, spans, "after " + _program_before(s, modules))]
                for s, e in gaps[: 4 * top]
            ],
            "gap_s": sum(e - s for s, e in gaps) * 1e-9,
            "n_gaps": len(gaps),
        }
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": {}, "host_spans": {}}
    host: dict = {}
    for name, _s, d in spans:
        row = host.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += d * 1e-9
    n = len(devices)
    return {
        "lines": {p: {ln: len(evs) for ln, evs in lines.items()} for p, lines in planes.items()
                  if p.startswith("/device:")},
        "window_s": max(d["window_s"] for d in devices.values()),
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "devices": devices,
        "host_spans": host,
    }


def reduce_dir(trace_dir: str) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)))


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ledger's view: the device ops that took most time (summed over
    the traced slice, first chip) and the longest idle gaps by what the
    benchmark's own spans say the host was doing."""
    if not reduced["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    ops = sorted(first["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    by_label: dict = {}
    for _start, seconds, label in first["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + seconds
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, row[1]] for name, row in ops],
        "idle_gaps": [[label, seconds] for label, seconds in gaps],
    }


def time_of(reduced: dict, pattern: str, device: int = 0) -> float:
    """Summed device seconds of the leaf ops whose name matches
    ``pattern`` (a regular expression), on one chip."""
    if not reduced["devices"]:
        return 0.0
    dev = reduced["devices"][sorted(reduced["devices"])[device]]
    rx = re.compile(pattern)
    return sum(row[1] for name, row in dev["ops"].items() if rx.search(name))


def program_durations(reduced: dict, pattern: str, device: int = 0) -> list:
    """Device seconds of each execution of the programs whose name matches."""
    if not reduced["devices"]:
        return []
    dev = reduced["devices"][sorted(reduced["devices"])[device]]
    rx = re.compile(pattern)
    return [d for name, ds in dev["programs"].items() if rx.search(name) for d in ds]
