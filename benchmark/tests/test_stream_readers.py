"""The six station readers on synthetic ``run`` dicts: a delta of two
bucket vectors gives the window's 95th percentile; a replica without a
``stream`` section, a missing key or a window of under 100 observations
gives None (the line then leaves the metric out, with a note)."""

import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness as H  # noqa: E402
from benchmark import stream_stations as S  # noqa: E402

READERS = {
    "emit_itl_p95_ms": "emit", "sent_itl_p95_ms": "sent",
    "acked_itl_p95_ms": "acked", "written_itl_p95_ms": "written",
    "stream_wake_p95_ms": "wake", "head_hold_p95_ms": "head_hold",
}
#: the program's boundaries, restated: 0.5 ms apart to 64 ms, then a factor
#: of the square root of two to 4.096 s
BOUNDS = [0.0005 * i for i in range(1, 129)] + [0.064 * 2 ** (i / 2) for i in range(1, 13)]


def bucketed(values):
    counts = [0] * (len(BOUNDS) + 1)
    for v in values:
        counts[next((i for i, b in enumerate(BOUNDS) if v <= b), len(BOUNDS))] += 1
    return counts


def run_of(key, opened, closed):
    def edge(values):
        return {"steps": 1, "stream": {"bounds_s": BOUNDS, key: bucketed(values)}}

    return {"counters": {"open": edge(opened), "close": edge(opened + closed)}}


@pytest.mark.parametrize("name,key", sorted(READERS.items()))
def test_a_reader_takes_the_windows_delta(name, key):
    rng = random.Random(hash(name) % 1000)
    before = [rng.uniform(0.2, 0.4) for _ in range(500)]  # the lead-in's: slow, not the window's
    window = sorted(rng.lognormvariate(0.0, 0.15) * 0.025 for _ in range(4000))
    value = H.load_metric("per_layer", name).read(run_of(key, before, window))
    exact_ms = 1e3 * window[int(0.95 * len(window)) - 1]
    assert abs(value - exact_ms) < 0.25, (value, exact_ms)


@pytest.mark.parametrize("name,key", sorted(READERS.items()))
def test_a_reader_with_nothing_to_read_answers_none(name, key):
    read = H.load_metric("per_layer", name).read
    window = [0.02] * 400
    other = next(k for k in READERS.values() if k != key)
    assert read({"counters": None}) is None
    assert read({"counters": {"open": {"steps": 1}, "close": {"steps": 9}}}) is None  # the parent's replica
    assert read(run_of(other, [], window)) is None          # the section lacks this key
    assert read(run_of(key, [], window[:99])) is None       # under 100 observations
    assert read(run_of(key, [], window[:100])) == pytest.approx(20.0, abs=0.5)
    short = run_of(key, [], window)
    short["counters"]["open"]["stream"][key] = [0, 0]       # vectors of another length
    assert read(short) is None


def test_the_overflow_bucket_reads_the_highest_bound():
    assert S.quantile_s([0.1, 0.2], [0, 0, 10], 0.95) == 0.2
    assert S.quantile_s([0.1, 0.2], [0, 0, 0], 0.95) is None
    assert S.quantile_s([0.1, 0.2], [10, 10, 0], 0.5) == pytest.approx(0.1)
    assert S.quantile_s([0.1, 0.2], [10, 10, 0], 0.75) == pytest.approx(0.15)


def test_the_six_readers_are_listed_for_the_four_serving_cells():
    man = H.manifest()
    serving = {w["name"] for w in man["workloads"]} - {"gpt2m_train"}
    for m in man["per_layer"]:
        if m["name"] in READERS:
            assert set(m["workloads"]) == serving and m["moves"] == "itl_p95_ms"
            assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_counter")
            assert m["layer"] == ("engine step loop" if m["name"].startswith("emit") else "streaming path")
    assert READERS.keys() <= {m["name"] for m in man["per_layer"]}
