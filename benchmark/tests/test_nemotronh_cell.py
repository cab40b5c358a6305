"""The cell ``nemotron3_agent_sat`` (ISSUE 66): its CPU rehearsal end to end,
the expert readers it lists (the decode's, which it shares with Granite's
cell) on a made-up trace with THIS family's counts, the family's counts against the issue's arithmetic (TWO matrices an
expert), and the file's sizes against the catalog row."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL, CONFIG = "nemotron3_agent_sat", "nemotron-3-nano-30b-ep8-1chip"
CALL = "custom-call(...), custom_call_target=\"tpu_custom_call\""
#: (HLO text, op_name) of the ops a step runs, 1 ms each, as a chip trace names
#: them: the expert kernels under ``moe_experts``, the shared expert beside
STEP = {
    "decode": [
        (f"%ssd_decode = f32[391,64,64,128] {CALL}",
         "jit(_decode_impl)/while/body/ssm/ssd_update/pallas_call"),
        (f"%paged_attention_verify = bf16[16,16,2,128] {CALL}",
         "jit(_decode_impl)/gqa_attention/paged_attention/pallas_call"),
        ("%fusion.3 = f32[16,128] fusion(...)", "jit(_decode_impl)/while/body/moe_router/dot"),
        (f"%moe_batch_experts = f32[16,2688] {CALL}",
         "jit(_decode_impl)/while/body/moe_experts/pallas_call"),
        ("%fusion.6 = f32[16,3712] fusion(...)", "jit(_decode_impl)/while/body/moe_shared/dot")],
    "prefill": [
        ("%fusion.7 = f32[512,128] fusion(...)", "jit(_prefill_impl)/while/body/moe_router/dot"),
        (f"%moe_grouped_experts = f32[512,2688] {CALL}",
         "jit(_prefill_impl)/while/body/moe_experts/pallas_call"),
        ("%fusion.8 = f32[512,3712] fusion(...)", "jit(_prefill_impl)/while/body/moe_shared/dot"),
        ("%fusion.9 = f32[512,2688] fusion(...)",
         "jit(_prefill_impl)/while/body/moe_shared/dot.2")],
}
#: what ``stats()`` gives at the slice's two ends
POOL = ({"decodes": 1000, "decode_rows": 16000, "decode_tokens": 1000 * 110000,
         "chunks": 200, "chunk_tokens": 200 * 500, "chunk_context_tokens": 200 * 3000},
        {"decodes": 1150, "decode_rows": 18400, "decode_tokens": 1150 * 110000,
         "chunks": 240, "chunk_tokens": 240 * 500, "chunk_context_tokens": 240 * 3000})
MOE = ({"decodes": 1000, "decode_pairs": 276000, "decode_touched": 198000,
        "decode_tile_rows": 198000 * 16, "decode_expert_steps": 198000,
        "chunks": 200, "chunk_pairs": 200 * 8832, "chunk_touched": 200 * 368,
        "chunk_tile_rows": 200 * 368 * 128, "chunk_expert_steps": 200 * 368},
       {"decodes": 1150, "decode_pairs": 317400, "decode_touched": 227700,
        "decode_tile_rows": 227700 * 16, "decode_expert_steps": 227700,
        "chunks": 240, "chunk_pairs": 240 * 8832, "chunk_touched": 240 * 368,
        "chunk_tile_rows": 240 * 368 * 128, "chunk_expert_steps": 240 * 368})
START, STOP = ({"state_pool": p, "kv_pool": p, "moe": m} for p, m in zip(POOL, MOE))
EXPERT = 2 * 2688 * 1856 * 2
ALWAYS = 2688 * 128 * 2 + 128 * 4 + 2 * 2688 * 3712 * 2


def _config():
    config = H.load_config(H.manifest(), CONFIG)
    return config, H.family_piece(config, "model_config")(H.sizes(config, False))


def _trace():
    """Two decodes and two prefill chunks, every op 1 ms, back to back."""
    modules, timed, names, t = [], [], {}, 0.0
    for program in ("decode", "prefill") * 2:
        start = t
        for hlo, op_name in STEP[program]:
            timed.append((hlo, t, 1e6))
            names[hlo] = op_name
            t += 1e6
        modules.append((start, t, f"jit__{program}_impl"))
    return {"ops": timed, "modules": modules, "op_names": names, "spans": []}


def _run(monkeypatch, peaks=True, ends=(START, STOP)):
    H.load_metric("per_layer", "moe_hybrid_decode_dev_ms")  # layer_metrics/ on the path
    import _inner_scope

    monkeypatch.setattr(_inner_scope, "load", lambda run: {"trace": _trace()})
    config, model = _config()
    counters = dict(zip(("trace_start", "trace_stop", "open", "close"), ends * 2))
    return {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
            "model": dataclasses.asdict(model), "counters": counters, "trace_dir": "x"}


def test_the_expert_readers_on_a_made_up_trace(monkeypatch, capsys):
    run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    peaks = run["peaks"]
    # a decode: router 1 + the batch kernel 1 + shared 1 ms; 198 of 368 touched
    assert read("moe_hybrid_decode_dev_ms") == pytest.approx(3.0)
    need = 23 * ALWAYS + 198 * EXPERT
    assert read("moe_hybrid_expert_roofline") == pytest.approx(
        100 * (need / peaks["hbm_bytes_per_s"]) / 3.0e-3, rel=1e-6)
    assert read("experts_touched_share") == pytest.approx(100 * 198 / 368)
    assert read("moe_tile_fill_share") == pytest.approx(100 * 276 / (198 * 16))
    assert read("moe_batch_form_share") == pytest.approx(100.0)
    # a chunk: every held expert touched, 24 pairs each
    assert read("moe_chunk_tile_fill_share") == pytest.approx(100 * 8832 / (368 * 128))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert lines[-1]["program"].startswith("decode") and lines[-1]["moe_bytes"] == need
    # the readers that REFUSE a shared expert read nothing here
    assert read("moe_chunk_dev_ms") is None and read("moe_chunk_expert_roofline") is None
    assert read("moe_routed_decode_dev_ms") is None


def test_the_cell_is_listed_where_a_reader_finds_something_to_read():
    listed = {m["name"] for m in H.manifest()["per_layer"] if CELL in m.get("workloads", [])}
    assert {"ssm_decode_dev_ms", "ssd_decode_roofline", "gqa_attn_dev_ms", "gqa_attn_roofline",
            "experts_touched_share", "moe_tile_fill_share", "moe_batch_form_share",
            "moe_chunk_tile_fill_share", "moe_hybrid_decode_dev_ms",
            "moe_hybrid_expert_roofline", "decode_step_dev_ms", "sampler_dev_ms",
            "batch_occupancy", "peak_hbm_gb", "device_idle_share"} <= listed
    # what times a prefill chunk in the traced slice: a closed loop on 16 slots
    # admits in waves and the slice held no chunk in 2 of 5 traced runs (PERF.md
    # section 7, Left by PR 66 (d)), so the cell is not on their lists
    assert not {"prefill_chunk_dev_ms", "ssd_chunk_dev_ms", "chunk_attn_dev_ms"} & listed
    # what refuses a ``moe_shared`` scope or crashes beside a ``state_pool``
    assert not {"moe_decode_dev_ms", "moe_expert_roofline", "moe_chunk_dev_ms",
                "moe_chunk_expert_roofline", "moe_routed_decode_dev_ms",
                "moe_routed_expert_roofline", "prefix_hit_share"} & listed
    assert CELL in next(m for m in H.manifest()["end_to_end"]
                        if m["name"] == "itl_p95_ms")["workloads"]


def test_the_familys_counts_are_the_issues_arithmetic():
    config, cfg = _config()
    model = dataclasses.asdict(cfg)
    piece = lambda name: H.family_piece(config, name)  # noqa: E731
    # TWO matrices an expert: 19.96 MB; the shared expert 39.9 MB
    assert EXPERT == 19_955_712 and ALWAYS == 688_128 + 512 + 39_911_424
    # a 16-row decode: 8.6 of 16 touched in each of 23 layers (54%)
    assert 16 * (1 - (122 / 128) ** 16) == pytest.approx(8.6, abs=0.05)
    assert piece("moe_decode_bytes")(198, model) == 23 * ALWAYS + 198 * EXPERT
    assert piece("moe_decode_bytes")(198, model) == pytest.approx(4.9e9, rel=2e-2)
    assert piece("moe_chunk_bytes")(368, model) == 23 * ALWAYS + 368 * EXPERT
    assert piece("moe_pair_flops")(model) == 4 * 2688 * 1856
    # 23 Mamba layers x 64 x 64 x 128 float32, in and out: 1.54 GB at 16 rows
    assert piece("ssd_decode_state_bytes")(16, model) == 16 * 23 * 2_097_152 * 2
    assert piece("ssd_decode_state_bytes")(16, model) == pytest.approx(1.54e9, rel=3e-3)
    # SIX attention layers of 2 heads of 128: 6,144 B a token
    assert piece("gqa_decode_kv_bytes")(1000, model) == 1000 * 6144
    # the counts are of the PUBLISHED width, not of the 1,920 the matrices are stored at
    assert cfg.stored(cfg.d_expert) == 1920 and model["d_expert"] == 1856
    # what the readers take of the model by name
    assert (model["n_layers"], model["n_dense_layers"], model["experts_held"]) == (52, 29, 16)
    assert piece("SERVE_MODEL") == "nemotron_h"


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` under the same key, but the ONE
    under ``reduced``, beside its published value; all 52 layers."""
    config, model = _config()
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True, "vocab_size": 131072,
    }
    assert {k: config[k] for k in published} == published
    assert {k: (config[k], v["published"]) for k, v in config["reduced"].items()} == {
        "n_routed_experts": (16, 128)}
    entry = next(c for c in H.manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["n_routed_experts"]
    dep = config["deployment"]
    assert (dep["router_experts"], dep["expert_parallel"], dep["expert_offset"],
            dep["pipeline_stages"], dep["chips"]) == (128, 8, 0, 1, 1)
    assert (model.n_routed_experts, model.experts_held, model.vocab_size, model.n_layers) == (
        128, 16, 131072, 52)
    assert (model.n_of("mamba"), model.n_of("attention"), model.n_of("moe")) == (23, 6, 23)
    eng = config["engine"]
    assert eng == {"max_slots": 16, "prefill_chunk": 512, "block_size": 128,
                   "max_blocks_per_seq": 74, "num_blocks": 1185, "spec_k": 0,
                   "prefix_cache": False}
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"] + 1
    assert eng["prefill_chunk"] == 4 * config["chunk_size"]
    traffic = H.load_traffic("agent_c32")
    assert traffic["kind"] == "closed_sessions" and traffic["clients"] == 32
    assert traffic["max_context"] == eng["max_blocks_per_seq"] * eng["block_size"] == 9472
    assert (traffic["system_prompt_len"] + traffic["user_len"][1] + traffic["max_tokens"][1]
            == traffic["max_context"])
    assert traffic["system_prompt_len"] + traffic["user_len"][0] == 4096
    lens = config["correctness"]["probe_prompt_lens"]
    assert any(n < 128 for n in lens) and any(512 < n for n in lens) and max(lens) >= 8000
    assert max(lens) + config["correctness"]["probe_out_tokens"] <= traffic["max_context"]


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
    assert ref["reference_ok"] and ref["pool_audit_ok"] and ref["prefix_audit_ok"]
    assert ref["reference"]["positions"] == 24 and ref["reference"]["max_deficit"] < 1e-3
    if trace:
        # the device's own counts are read: touched experts, and pairs a computed row
        assert 0 < last["metrics"]["experts_touched_share"]["value"] <= 100
        assert 0 < last["metrics"]["moe_tile_fill_share"]["value"] <= 100
        assert last["metrics"]["moe_batch_form_share"]["value"] == 100.0
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
