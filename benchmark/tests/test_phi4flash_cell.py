"""The cell ``phi4flash_reason_sat`` (ISSUE 42): its CPU rehearsal end to
end, and its four new readers on a recorded reading of the chip: the scopes'
seconds of one traced run (PERF.md, section 5, my chip run, PR 42) beside the
counters that run's ``stats()`` gave at the slice's two ends."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL = "phi4flash_reason_sat"
#: device seconds in 150 decodes by scope, and the engine's account around them
SCOPES = {"shared_kv_attention": {"paged_attention_verify tpu_custom_call": 0.420, "fusion.1": 0.03},
          "window_attention": {"paged_attention_verify tpu_custom_call": 0.300},
          "ssm": {"fusion.7": 0.150, "scatter.2": 0.06}, "mlp": {"fusion.2": 0.9}}
START = {"decodes": 1000, "decode_rows": 32000, "decode_tokens": 32000 * 800}
STOP = {"decodes": 1150, "decode_rows": 36800, "decode_tokens": 32000 * 800 + 150 * 32 * 850}


def _run(monkeypatch, peaks=True, state_pool=(START, STOP)):
    reader = H.load_metric("per_layer", "ssm_decode_dev_ms")  # layer_metrics/ on the path
    import _decode_scope

    monkeypatch.setattr(_decode_scope, "load", lambda run: {
        "decodes": 150, "decode_by_scope": SCOPES})
    config = H.load_config(H.manifest(), "phi4-mini-flash-1chip")
    import dataclasses

    model = dataclasses.asdict(H.family_piece(config, "model_config")(H.sizes(config, False)))
    counters = {at: {"state_pool": sp} for at, sp in zip(("trace_start", "trace_stop"), state_pool)}
    return reader, {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
                    "model": model, "counters": counters, "trace_dir": "x"}


def test_the_new_readers_on_a_recorded_reading(monkeypatch, capsys):
    ssm, run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    assert ssm.read(run) == pytest.approx(1.4)
    assert read("window_attn_dev_ms") == pytest.approx(2.0)
    assert read("shared_kv_attn_dev_ms") == pytest.approx(3.0)
    # 8 readers x 32 rows x 850 tokens x 5,120 B = 1.114 GB: 1.36 ms at 819 GB/s
    assert read("shared_kv_attn_roofline") == pytest.approx(
        100 * (8 * 32 * 850 * 5120 / 819e9) / 3.0e-3, rel=1e-6)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 4 and all(
        x["event"] == "program_spans" and x["live_rows"] == 32 and x["live_tokens"] == 32 * 850
        and x["between"] == ["trace_start", "trace_stop"] for x in lines)
    assert lines[-1]["scope"] == "shared_kv_attention" and lines[-1]["kv_bytes"] == 1114112000


def test_the_roofline_needs_a_chip_and_the_programs_count(monkeypatch):
    _, run = _run(monkeypatch, peaks=False)
    assert H.load_metric("per_layer", "shared_kv_attn_roofline").read(run) is None
    # the parent of PR 42 counts no decode_tokens and has no such scope
    old = ({"decodes": 1, "decode_rows": 2}, {"decodes": 5, "decode_rows": 9})
    _, run = _run(monkeypatch, state_pool=old)
    assert H.load_metric("per_layer", "shared_kv_attn_roofline").read(run) is None
    import _decode_scope

    monkeypatch.setattr(_decode_scope, "load", lambda run: {
        "decodes": 150, "decode_by_scope": {"mlp": {"fusion.2": 0.9}}})
    for name in ("ssm_decode_dev_ms", "window_attn_dev_ms", "shared_kv_attn_dev_ms",
                 "shared_kv_attn_roofline"):
        assert H.load_metric("per_layer", name).read(run) is None
    monkeypatch.setattr(_decode_scope, "load", lambda run: None)
    assert H.load_metric("per_layer", "ssm_decode_dev_ms").read(run) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_reads_correct(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3000000011", "--seconds", "4", "--trace", str(trace), "--rehearsal"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["event"] == "rehearsal_result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    ref = next(x for x in lines if x["event"] == "correctness")  # may be a cached verdict
    assert ref["reference_ok"] and ref["pool_audit_ok"]
    assert ref["reference"]["positions"] == 24 and ref["reference"]["max_deficit"] < 1e-3
    if not trace:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
