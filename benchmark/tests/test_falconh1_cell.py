"""The cell ``falconh1_longdoc_sat`` (ISSUE 49): its CPU rehearsal end to
end, the manifest's lists, the family's counts, and its five new readers on a
made-up trace (op names as a chip trace has them: the SSD scopes lie INSIDE
``ssm``) beside the counters a run's ``stats()`` would give at the slice's two
ends."""

import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL, CONFIG = "falconh1_longdoc_sat", "falcon-h1-34b-l8-1chip"
NEW = ("ssd_decode_roofline", "ssd_chunk_dev_ms", "gqa_attn_dev_ms", "gqa_attn_roofline",
       "chunk_attn_dev_ms")
KERNEL = "%ssd_decode = f32[136,32,128,256] custom-call(...), custom_call_target=\"tpu_custom_call\""
ATTN = "%paged_attention_decode = bf16[16,20,128] custom-call(...), custom_call_target=\"tpu_custom_call\""
#: (HLO text, op_name) of the ops a decode and a prefill chunk run, 1 ms each
OPS = {
    "decode": [(KERNEL, "jit(_decode_impl)/while/body/ssm/ssd_update/pallas_call"),
               ("%fusion.1 = f32[16,32,256] fusion(...)",
                "jit(_decode_impl)/while/body/ssm/ssd_update/mul"),
               ("%fusion.2 = f32[16,9248] fusion(...)", "jit(_decode_impl)/while/body/ssm/dot"),
               (ATTN, "jit(_decode_impl)/while/body/gqa_attention/paged_attention/pallas_call"),
               ("%fusion.3 = f32[16,21504] fusion(...)", "jit(_decode_impl)/while/body/mlp/dot")],
    "prefill": [("%fusion.4 = f32[4,32,128,128] fusion(...)",
                 "jit(_prefill_impl)/while/body/ssm/ssd_chunk/dot_general"),
                ("%fusion.5 = f32[4,2560,512] fusion(...)",
                 "jit(_prefill_impl)/while/body/chunk_attention/while/body/exp"),
                ("%fusion.6 = f32[4,2560,512] fusion(...)",
                 "jit(_prefill_impl)/while/body/chunk_attention/while/body/dot_general")],
}
START = {"decodes": 100, "decode_rows": 1600, "decode_tokens": 1600 * 7000,
         "chunks": 90, "chunk_tokens": 90 * 500, "chunk_context_tokens": 90 * 4000}
STOP = {"decodes": 102, "decode_rows": 1632, "decode_tokens": 1600 * 7000 + 2 * 16 * 7400,
        "chunks": 93, "chunk_tokens": 90 * 500 + 3 * 512,
        "chunk_context_tokens": 90 * 4000 + 3 * 4200}


def _trace():
    """Two decodes and three chunks, every op 1 ms, programs back to back."""
    modules, ops, names, t = [], [], {}, 0.0
    for program, n in (("decode", 2), ("prefill", 3)):
        for _ in range(n):
            start = t
            for hlo, op_name in OPS[program]:
                ops.append((hlo, t, 1e6))
                names[hlo] = op_name
                t += 1e6
            modules.append((start, t, f"jit__{program}_impl"))
    return {"ops": ops, "modules": modules, "op_names": names, "spans": []}


def _run(monkeypatch, peaks=True, state_pool=(START, STOP), trace=None):
    H.load_metric("per_layer", NEW[0])  # layer_metrics/ on the path
    import _inner_scope

    monkeypatch.setattr(_inner_scope, "load", lambda run: {"trace": trace or _trace()})
    config = H.load_config(H.manifest(), CONFIG)
    model = dataclasses.asdict(H.family_piece(config, "model_config")(H.sizes(config, False)))
    counters = {at: {"state_pool": sp} for at, sp in zip(("trace_start", "trace_stop"), state_pool)}
    return {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
            "model": model, "counters": counters, "trace_dir": "x"}


def test_the_new_readers_on_a_made_up_trace(monkeypatch, capsys):
    run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    # the kernel ALONE: 1 of the 2 ms a decode under ssd_update (which lies in ssm)
    state = 16 * 8 * 32 * 128 * 256 * 4 * 2
    assert read("ssd_decode_roofline") == pytest.approx(100 * (state / 819e9) / 1e-3, rel=1e-6)
    assert read("gqa_attn_dev_ms") == pytest.approx(1.0)
    assert read("gqa_attn_roofline") == pytest.approx(
        100 * (16 * 7400 * 8 * 2048 / 819e9) / 1e-3, rel=1e-6)
    assert read("ssd_chunk_dev_ms") == pytest.approx(1.0)
    assert read("chunk_attn_dev_ms") == pytest.approx(2.0)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 5 and all(x["event"] == "program_spans" for x in lines)
    first, last = lines[0], lines[-1]
    assert first["scope"] == "ssd_update" and first["kernel_only"] and first["executed"] == 2
    assert first["live_rows"] == 16 and first["state_bytes"] == state
    assert lines[2]["live_tokens"] == 16 * 7400 and lines[2]["kv_bytes"] == 16 * 7400 * 16384
    assert last["scope"] == "chunk_attention" and last["executed"] == 3
    assert last["chunks"] == 3 and last["chunk_tokens"] == 512
    assert last["chunk_context_tokens"] == 4200 and last["between"] == ["trace_start", "trace_stop"]


def test_the_readers_find_nothing_on_a_program_without_the_scopes(monkeypatch):
    """The parent of PR 49 (or another family's cell): no such scope, no chunk
    counters; a rehearsal: no peaks; no trace at all.  None, never an error."""
    other = _trace()
    other["op_names"] = {k: re.sub(r"ssm/ssd_update|ssm/ssd_chunk|gqa_attention|chunk_attention",
                                   "mlp", v) for k, v in other["op_names"].items()}
    old = ({"decodes": 1, "decode_rows": 2}, {"decodes": 5, "decode_rows": 9})
    run = _run(monkeypatch, trace=other, state_pool=old)
    for name in NEW:
        assert H.load_metric("per_layer", name).read(run) is None, name
    run = _run(monkeypatch, peaks=False)
    assert H.load_metric("per_layer", "ssd_decode_roofline").read(run) is None
    assert H.load_metric("per_layer", "gqa_attn_roofline").read(run) is None
    import _inner_scope

    monkeypatch.setattr(_inner_scope, "load", lambda run: None)
    for name in NEW:
        assert H.load_metric("per_layer", name).read(run) is None, name


def test_the_manifests_lists_and_the_files_sizes():
    man = H.manifest()
    cell = H.find_workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "docqa_c32", 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    config = H.load_config(man, CONFIG)
    assert entry["reduced"] == sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert [m["name"] for m in H.metrics_for(man, "end_to_end", CELL)] == ["itl_p95_ms", "setup_s"]
    listed = {m["name"]: m for m in H.metrics_for(man, "per_layer", CELL)}
    assert set(NEW) <= set(listed) and all(
        listed[n]["workloads"] == [CELL] and listed[n]["moves"] == "itl_p95_ms" for n in NEW)
    # every per-layer metric the other closed serving cells report, and the scan's
    other = {m["name"] for m in H.metrics_for(man, "per_layer", "phi4flash_reason_sat")}
    assert other - set(listed) == {"window_attn_dev_ms", "shared_kv_attn_dev_ms",
                                   "shared_kv_attn_roofline"}
    assert "prefill_chunk_dev_ms" in listed and "ssm_decode_dev_ms" in listed
    # the published widths, every multiplier, and the engine as the issue fixed it
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["intermediate_size"], config["mamba_d_ssm"],
            config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"]) == (
                5120, 20, 4, 128, 21504, 4096, 32, 128, 256, 2, 4)
    assert config["ssm_multipliers"] == [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                         0.3535533905932738]
    assert config["engine"] == {"max_slots": 16, "prefill_chunk": 512, "block_size": 128,
                                "max_blocks_per_seq": 100, "num_blocks": 1601, "spec_k": 0,
                                "prefix_cache": False}
    traffic = H.load_traffic("docqa_c32")
    assert (traffic["kind"], traffic["clients"], traffic["sessions_per_client"]) == (
        "closed_sessions", 32, 12)
    assert traffic["user_len"] == [2048, 12288] and traffic["max_tokens"] == [128, 384]
    assert traffic["max_context"] == 12800 == 100 * 128 and not traffic["prime"]
    assert traffic["system_prompt_len"] + 12288 + 384 <= traffic["max_context"]


def test_the_familys_counts():
    config = H.load_config(H.manifest(), CONFIG)
    model = dataclasses.asdict(H.family_piece(config, "model_config")(H.sizes(config, False)))
    piece = lambda name: H.family_piece(config, name)  # noqa: E731
    assert piece("ssd_decode_state_bytes")(1, model) == 8 * 4194304 * 2
    assert piece("gqa_decode_kv_bytes")(1, model) == 8 * 2048
    with pytest.raises(AssertionError):
        H.family_piece(config, "model_config")(dict(config, mamba_norm_before_gate=True))
    with pytest.raises(AssertionError):
        H.family_piece(config, "model_config")(dict(config, attn_layer_indices=[0, 4]))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(H.BENCH_DIR, "reference", "falcon_h1.py")) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import)\s+ray_tpu", source, re.M)


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
