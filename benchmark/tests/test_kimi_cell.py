"""The cell ``kimi_longdoc_sat`` (ISSUE 45): its CPU rehearsal end to end,
and its five new readers on a recorded reading: the scopes' seconds of a
traced slice beside the counters ``stats()`` gives at the slice's two ends
(``kv_pool`` the engine's own, ``moe`` counted on the device)."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL, CONFIG = "kimi_longdoc_sat", "kimi-k2.5-ep32-l7-1chip"
#: device seconds in 150 decodes by scope, and the program's account around them
SCOPES = {"latent_attention": {"latent_attention_decode tpu_custom_call": 0.600, "fusion.9": 0.03},
          "moe_router": {"fusion.3": 0.045}, "moe_experts": {"while.2": 0.300},
          "moe_shared": {"fusion.4": 0.105}, "mla_proj": {"fusion.2": 0.5}}
TOKENS = 16 * 17000
START = {"kv_pool": {"decodes": 1000, "decode_rows": 16000, "decode_tokens": 1000 * TOKENS},
         "moe": {"decodes": 1003, "decode_pairs": 24000, "decode_touched": 21000}}
STOP = {"kv_pool": {"decodes": 1150, "decode_rows": 18400, "decode_tokens": 1150 * TOKENS},
        "moe": {"decodes": 1153, "decode_pairs": 27600, "decode_touched": 24150}}


def _run(monkeypatch, peaks=True, ends=(START, STOP), scopes=SCOPES):
    reader = H.load_metric("per_layer", "latent_attn_dev_ms")  # layer_metrics/ on the path
    import _decode_scope

    monkeypatch.setattr(_decode_scope, "load", lambda run: scopes and {
        "decodes": 150, "decode_by_scope": scopes})
    config = H.load_config(H.manifest(), CONFIG)
    model = dataclasses.asdict(H.family_piece(config, "model_config")(H.sizes(config, False)))
    counters = dict(zip(("trace_start", "trace_stop"), ends))
    return reader, {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
                    "model": model, "counters": counters, "trace_dir": "x"}


def test_the_new_readers_on_a_recorded_reading(monkeypatch, capsys):
    latent, run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    assert latent.read(run) == pytest.approx(4.2)
    assert read("moe_decode_dev_ms") == pytest.approx(3.0)
    # 7 layers x 272,000 tokens x 1,152 B = 2.193 GB: 2.68 ms at 819 GB/s; 64 heads x
    # (576 + 512) x 2 = 139,264 operations a token a layer: 265 GFLOP, 1.35 ms at 197 TFLOP/s
    assert read("latent_attn_roofline") == pytest.approx(
        100 * (7 * TOKENS * 1152 / 819e9) / 4.2e-3, rel=1e-6)
    # 6 x (router 5.5 MB + shared 88.1 MB) + 21 touched x 88.1 MB = 2.41 GB
    need = 6 * (7168 * 384 * 2 + 3 * 7168 * 2048 * 2) + 21 * 3 * 7168 * 2048 * 2
    assert read("moe_expert_roofline") == pytest.approx(100 * (need / 819e9) / 3.0e-3, rel=1e-6)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert all(x["event"] == "program_spans" and x["live_rows"] == 16
               and x["live_tokens"] == TOKENS and x["touched"] == 21 and x["pairs"] == 24
               and x["between"] == ["trace_start", "trace_stop"] for x in lines)
    roof = next(x for x in lines if "least_ms_by_bytes" in x)
    assert roof["least_ms_by_bytes"] == pytest.approx(2.678, rel=1e-3)
    assert roof["least_ms_by_flops"] == pytest.approx(1.346, rel=1e-3)
    assert lines[-1]["scope"] == "moe_shared" and lines[-1]["moe_bytes"] == need
    # the window's counters: 21 of 72 (layer, expert) slots touched a decode
    run["counters"] = {"open": START, "close": STOP}
    assert read("experts_touched_share") == pytest.approx(100 * 21 / 72)


def test_the_readers_need_a_chip_the_scopes_and_the_programs_counts(monkeypatch):
    _, run = _run(monkeypatch, peaks=False)
    for name in ("latent_attn_roofline", "moe_expert_roofline"):
        assert H.load_metric("per_layer", name).read(run) is None
    names = ("latent_attn_dev_ms", "latent_attn_roofline", "moe_decode_dev_ms",
             "moe_expert_roofline", "experts_touched_share")
    # the parent of PR 45: no such counters, no such scopes, and it does not raise
    for ends in (({}, {}), ({"state_pool": {"decodes": 1}}, {"state_pool": {"decodes": 5}})):
        _, run = _run(monkeypatch, ends=ends)
        run["counters"].update(open=ends[0], close=ends[1])
        assert [H.load_metric("per_layer", n).read(run) for n in names] == [None] * 5
    _, run = _run(monkeypatch, scopes={"mlp": {"fusion.2": 0.9}})
    assert [H.load_metric("per_layer", n).read(run) for n in names[:4]] == [None] * 4
    # one of the three expert scopes missing is no reading of the expert layers
    _, run = _run(monkeypatch, scopes={k: v for k, v in SCOPES.items() if k != "moe_shared"})
    assert H.load_metric("per_layer", "moe_decode_dev_ms").read(run) is None
    _, run = _run(monkeypatch, scopes=None)
    assert H.load_metric("per_layer", "latent_attn_dev_ms").read(run) is None


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` under the same key, but the
    three under ``reduced``, each beside its published value."""
    config = H.load_config(H.manifest(), CONFIG)
    published = {
        "first_k_dense_replace": 1, "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 262144, "moe_intermediate_size": 2048,
        "n_shared_experts": 1, "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.827, "v_head_dim": 128, "ep_size": 1, "n_group": 1,
        "topk_group": 1, "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
    }
    assert {k: config[k] for k in published} == published
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert {k: (config[k], v["published"]) for k, v in config["reduced"].items()} == {
        "num_hidden_layers": (7, 61), "n_routed_experts": (12, 384), "vocab_size": (20480, 163840)}
    entry = next(c for c in H.manifest()["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    dep = config["deployment"]
    assert (dep["router_experts"], dep["expert_parallel"], dep["expert_offset"]) == (384, 32, 0)
    model = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (model.n_routed_experts, model.experts_held, model.vocab_size) == (384, 12, 20480)
    # the margin the reference leaves rows open by is the one the file states
    from benchmark.reference import kimi_k2 as reference

    assert config["correctness"]["routing_margin"] == reference.ROUTING_MARGIN
    eng = config["engine"]
    assert (eng["num_blocks"] - 1) * eng["block_size"] == 262144
    assert eng["max_blocks_per_seq"] * eng["block_size"] >= 17664 and eng["prefix_cache"]


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
    assert ref["prefix_hit_identical"] and ref["jit_cache_sizes"]["fork"] == 1
    assert ref["reference"]["positions"] == 24 and ref["reference"]["max_deficit"] < 1e-3
    if trace:
        # the radix tree serves latent blocks, and the device's own count is read
        assert last["metrics"]["prefix_hit_share"]["value"] > 50
        assert 0 < last["metrics"]["experts_touched_share"]["value"] <= 100
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
