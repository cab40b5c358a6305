"""The seven readers of ISSUE 23 (``layer_metrics/_program_spans/`` and
its users) on synthetic counters and traces, on an xplane recorded on the
v5e, and in a CPU rehearsal of a traced serving cell."""

import gzip
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness as H

HERE = os.path.dirname(os.path.abspath(__file__))
BOUNDS = [0.001 * 2 ** (i / 2) for i in range(33)]


def reader(name):
    return H.load_metric("per_layer", name)


def spans_module():
    reader("sampler_dev_ms")  # puts layer_metrics/ on the path
    import _program_spans

    return _program_spans


def counters(**close):
    """``stats()`` at the window's edges: zeros at the opening, ``close``
    at the close (nested keys as ``a.b``)."""
    hist = [0] * 34

    def tree(leaf):
        return {
            "steps": leaf(400), "t_read": leaf(50.0),
            "step_phase_s": {k: leaf(v) for k, v in dict(
                admit=0.4, prefill_build=0.2, prefill_launch=0.4, prefill_sample=9.0,
                draft=0.0, decode_build=0.8, decode_launch=0.4, decode_fetch=30.0,
                emit=1.2, publish=0.6).items()},
            "loop": {"step_wall_s": leaf(49.0), "lock_wait_s": leaf(0.25), "idle_s": leaf(0.25),
                     "step_wall_hist": hist, "step_wall_bounds_s": BOUNDS},
            "submit": {"n": leaf(40), "lock_wait_s": leaf(120.0), "lock_wait_max_s": leaf(9.0)},
            "queue": {"admitted": leaf(50), "wait_s": leaf(5.0)},
        }

    opening, closing = tree(lambda v: type(v)(0)), tree(lambda v: v)
    closing["loop"]["step_wall_hist"] = [0] * 14 + [300, 99, 0, 0, 0, 0, 0, 0, 1] + [0] * 11
    for path, v in close.items():
        d = closing
        *head, last = path.split(".")
        for p in head:
            d = d[p]
        d[last] = v
    return {"open": opening, "close": closing}


@pytest.mark.parametrize("name,want", [
    ("step_host_ms", 1e3 * (0.4 + 0.2 + 0.4 + 0.8 + 0.4 + 1.2 + 0.6) / 400),
    # one step in the window fell in the bucket whose upper bound is 2 s
    ("step_wall_max_ms", 1e3 * BOUNDS[22]),
    ("loop_outside_step_share", 100 * 0.5 / 50.0),
    ("submit_lock_wait_ms", 1e3 * 120.0 / 40),
    ("queue_wait_ms", 1e3 * 5.0 / 50),
])
def test_counter_readers_take_deltas_of_the_programs_own_account(name, want):
    assert BOUNDS[22] == pytest.approx(2.048)
    assert reader(name).read({"counters": counters()}) == pytest.approx(want)


def test_rates_divide_by_the_readings_own_clock_not_the_nominal_window():
    run = {"counters": counters(**{"t_read": 77.0})}  # the closing stats() waited 27 s
    assert reader("loop_outside_step_share").read(run) == pytest.approx(100 * 0.5 / 77.0)


@pytest.mark.parametrize("name", [
    "step_host_ms", "step_wall_max_ms", "loop_outside_step_share", "submit_lock_wait_ms",
    "queue_wait_ms", "idle_attributed_share", "sampler_dev_ms",
])
def test_an_older_replica_is_nothing_to_read_not_an_error(name):
    old = {"steps": 10, "tokens_generated": 5}
    run = {"counters": {"open": dict(old), "close": dict(old, steps=400)}, "trace_dir": None}
    assert reader(name).read(run) is None
    assert reader(name).read({"counters": None, "trace_dir": None}) is None


def test_a_gap_goes_to_the_phase_span_that_covers_it():
    ps = spans_module()
    ms = 1e6
    trace = {
        # device: decode 0-80, idle 80-86, prefill 86-140, idle 140-141, decode 141-200
        "ops": [("a", 0.0, 80 * ms), ("b", 86 * ms, 54 * ms), ("c", 141 * ms, 59 * ms)],
        "modules": [(0.0, 80 * ms, "jit__decode_impl"), (86 * ms, 140 * ms, "jit__prefill_impl"),
                    (141 * ms, 200 * ms, "jit__decode_impl")],
        "spans": sorted([
            ("llm.step", 0.0, 84 * ms), ("llm.step.decode_fetch", 1 * ms, 80.5 * ms),
            ("llm.step.emit", 80.5 * ms, 83 * ms), ("llm.step.publish", 83 * ms, 84 * ms),
            ("llm.loop.lock_wait", 84 * ms, 84.2 * ms), ("llm.step", 84.2 * ms, 300 * ms),
            ("llm.step.admit", 84.2 * ms, 85 * ms), ("llm.step.prefill_launch", 85 * ms, 86.5 * ms),
            ("llm.submit.lock_wait", 0.0, 300 * ms),  # a caller's thread: explains nothing
            ("llm.step.decode_launch", 140.2 * ms, 141.5 * ms),
        ], key=lambda s: s[1]),
        "op_names": {},
    }
    idle, covered, by_span = ps.idle_by_span(trace, 0.0)
    assert idle == pytest.approx(0.007)
    assert by_span["llm.step.emit"] == pytest.approx(0.0025)
    assert by_span["llm.step.decode_fetch"] == pytest.approx(0.0005)
    assert by_span["llm.step.publish"] == pytest.approx(0.001)
    assert by_span["llm.step.admit"] == pytest.approx(0.0008)
    assert by_span["llm.step.decode_launch"] == pytest.approx(0.0008)
    assert "llm.step" not in by_span and "llm.submit.lock_wait" not in by_span
    assert covered == pytest.approx(sum(by_span.values())) and covered < idle
    # the device's clock 0.5 ms ahead of the host's: shifted back, fetch covers nothing
    shifted = ps.idle_by_span(trace, 0.5 * ms)[2]
    assert "llm.step.decode_fetch" not in shifted
    assert shifted["llm.step.emit"] == pytest.approx(0.0025)


def test_the_clock_offset_lies_between_launch_and_fetch():
    ps = spans_module()
    ms = 1e6
    trace = {"modules": [(10 * ms, 90 * ms, "jit__decode_impl"), (110 * ms, 190 * ms, "jit__decode_impl")],
             "spans": [("llm.step.decode_launch", 10.6 * ms, 11 * ms),
                       ("llm.step.decode_fetch", 11 * ms, 91.9 * ms),
                       ("llm.step.decode_launch", 100 * ms, 101 * ms),  # queued behind a chunk
                       ("llm.step.decode_fetch", 101 * ms, 192 * ms)]}
    offset, lo, hi = ps.clock_offset_ns(trace)
    assert lo == pytest.approx(0.6 * ms) and hi == pytest.approx(1.9 * ms)
    assert offset == pytest.approx(1.25 * ms)
    assert ps.clock_offset_ns({"modules": [], "spans": []}) == (0.0, None, None)
    # every decode queued behind a chunk: the lower bound says nothing
    trace["spans"][0] = ("llm.step.decode_launch", -40 * ms, -39 * ms)
    offset, lo, hi = ps.clock_offset_ns(trace)
    assert lo == pytest.approx(-10 * ms) and offset == pytest.approx(hi - 1.5 * ms)


def test_the_recorded_v5e_xplane_gives_spans_scopes_and_the_kernels_name(tmp_path, monkeypatch):
    """``make_xplane_probe.py`` says how it was recorded."""
    ps = spans_module()
    path = tmp_path / "probe.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "xplane_v5e_probe.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = ps.read_trace(str(path))
    assert len(trace["modules"]) == 10 and len(trace["ops"]) > 100
    assert {n for n, _s, _e in trace["spans"]} == {
        "llm.step", "llm.step.decode_launch", "llm.step.decode_fetch", "llm.step.emit"}
    # the op_name is in the event metadata, which ProfileData does not show
    sort = next(v for k, v in trace["op_names"].items() if k.startswith("%sort"))
    assert sort == "jit(step)/sample/jit(sort)/sort:"
    program = re.compile("jit_step")
    by_scope = ps.seconds_by_scope(trace, program)
    assert list(by_scope)[0] == "sample" and {"qkv", "mlp", "paged_attention"} <= set(by_scope)
    ops = by_scope["sample"]
    assert 1.5e-3 < sum(ops.values()) / 5 < 2.5e-3  # five steps; the sort, 1.87 ms a step
    assert max(ops, key=ops.get).startswith("sort")
    assert list(by_scope["paged_attention"]) == [
        "probe_kernel.3 bf16[128,512] custom-call tpu_custom_call"]
    monkeypatch.setattr(ps, "DECODE_PROGRAM", program)
    offset, lo, hi = ps.clock_offset_ns(trace)
    # this trace cannot tell the clocks apart by more than -0.6..+1.7 ms
    assert -1e6 < lo < offset < hi < 3e6
    idle, covered, by_span = ps.idle_by_span(trace, offset)
    assert 0 < covered <= idle and max(by_span, key=by_span.get) == "llm.step.emit"


def test_a_traced_rehearsal_reads_the_new_counters_and_no_less_than_before():
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", "gptj_chat_r80_v2",
         "--seed", "3", "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    last = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")][-1]
    assert last["event"] == "rehearsal_result" and last["correct"] is True
    got = set(last["metrics"])
    # a CPU has no device plane: the trace readers, old and new, read nothing
    assert got == {"step_host_ms", "step_wall_max_ms", "submit_lock_wait_ms", "queue_wait_ms"}
    left_out = set(re.findall(r"per_layer metric (\w+): nothing to read", proc.stderr))
    old = {m["name"] for m in H.manifest()["per_layer"][:15] if "gptj_chat_r80_v2" in m["workloads"]}
    assert old == {"decode_step_dev_ms", "prefill_chunk_dev_ms", "paged_attn_roofline",
                   "device_idle_share"}
    # every old reader still ran to its end (it needs a chip's trace, as before)
    assert old | {"idle_attributed_share", "sampler_dev_ms"} == left_out


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_decode_impl)/jit(main)/while/body/qkv/dot_general:", "qkv"),
    ("jit(_decode_impl)/jit(main)/sample/vmap(jit(sort))/sort:", "sample"),
    ("jit(_decode_impl)/jit(main)/while/body/dynamic_slice:", "-"),  # the scan's own copy
    ("jit(step)/transpose(jvp(ce))/while/body/add:", "ce"),
    ("jit(step)/transpose(jvp(while))/body/checkpoint/attn/mul:", "attn"),
    ("jit(step)/optimizer/add:", "optimizer"), ("", "-"),
])
def test_the_outermost_named_scope_of_an_op(op_name, scope):
    assert spans_module().scope_of(op_name) == scope
